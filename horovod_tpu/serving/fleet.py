"""Self-healing serving fleet: replica supervision, hot-spare
promotion, rolling drain/restart, and crash-loop quarantine.

PR 10's transport makes *requests* survive a dead replica — the
dispatcher routes around it, breakers open, failover resubmits. Nothing
makes *capacity* survive: a crashed replica shrinks the fleet forever.
This module is the keep-the-world-size discipline of "Highly Available
Data Parallel ML training on Mesh Networks" (PAPERS.md) applied to the
inference side, mirroring ``run_elastic(spares=N)``:

* :class:`FleetSupervisor` owns replica processes end-to-end: it spawns
  them through a pluggable *launcher*, watches liveness (process exit +
  a ``status`` health RPC whose heartbeat ``seq`` the transport already
  maintains), and **restarts** crashed replicas with jittered
  exponential backoff under a bounded per-replica restart budget.
* **Crash loops** are detected — K deaths inside a sliding window, or a
  spent restart budget — and the replica is **quarantined** with a
  typed reason instead of burning respawns forever.
* Optional **warm spares** (engine compiled, programs warmed, idle but
  unlisted) are *promoted* into a dead rank's slot the moment the death
  is observed, so serving capacity holds at the target while the dead
  replica rebuilds in the background as the new spare.
* :meth:`FleetSupervisor.rolling_restart` drains one replica at a time
  (the transport's ``drain`` RPC flips the engine to draining: queued
  and active work finishes, new submits bounce retryable and re-place
  through the dispatcher), restarts it, waits for readmission (fresh
  breaker closed, status probe healthy), then moves on — zero dropped
  requests, at most one replica unavailable at a time.
* Membership is published to an atomically-rewritten JSON file that
  :class:`~horovod_tpu.serving.transport.RemoteDispatcher` follows
  (``membership=`` path): joins/readmissions install fresh clients with
  fresh CLOSED breakers, so a respawned replica serves again without a
  dispatcher process restart.

Deterministic failure driving rides :mod:`horovod_tpu.faults`:
``crash_loop@rank=R,step=S,count=N`` SIGKILLs a replica at its Sth
inbound RPC on every fleet attempt below N, and
``flap@rank=R,step=S,period=P,seconds=X`` bounces its link.

Observability: ``fleet_replicas{state}`` /``fleet_target_replicas``
gauges, ``fleet_restarts_total{replica,reason}``,
``fleet_promotion_seconds``, ``rolling_restart_seconds``, ``FLEET``
timeline markers, and a ``hvd.doctor()`` ``_check_fleet`` finding for
quarantines, capacity below target, and restart burn — each naming the
``HOROVOD_SERVE_FLEET_*`` knobs validated in ``config.py``. Exercised
end-to-end by ``tools/fleet_smoke.py`` (``make fleet-smoke``).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

from horovod_tpu import metrics
from horovod_tpu.serving.transport import (
    RemoteClient, TransportError, backoff_delays,
)

__all__ = ["FleetSupervisor", "ReplicaSlot", "ProcessLauncher",
           "ProcessReplica"]

# Lifecycle states a slot reports (the `state` label of fleet_replicas).
LIVE = "live"
STARTING = "starting"
RESTARTING = "restarting"
QUARANTINED = "quarantined"
SPARE = "spare"            # display state: live but held out of serving


def _note_fleet(event: str, **fields: Any) -> None:
    """Mirror a FLEET transition into the flight recorder's events ring
    (blackbox.py; no-op unless HOROVOD_BLACKBOX) — the supervisor's own
    postmortem bundle then carries the slot state machine's history."""
    try:
        from horovod_tpu import blackbox
        blackbox.note_fleet(event, **fields)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# process launcher (fleet_smoke / production); tests inject their own
# ---------------------------------------------------------------------------

class ProcessReplica:
    """Handle for one spawned replica process.

    Address discovery is file-based and attempt-suffixed
    (``port.rank{R}.a{A}`` under ``root``) so a respawn can never be
    mistaken for its dead predecessor's stale port file."""

    def __init__(self, proc: subprocess.Popen, root: str, rank: int,
                 attempt: int):
        self.proc = proc
        self.root = root
        self.rank = int(rank)
        self.attempt = int(attempt)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def address(self) -> Optional[Tuple[str, int]]:
        tag = f"rank{self.rank}.a{self.attempt}"
        ready = os.path.join(self.root, f"ready.{tag}")
        port = os.path.join(self.root, f"port.{tag}")
        if not (os.path.exists(ready) and os.path.exists(port)):
            return None
        try:
            with open(port) as f:
                return ("127.0.0.1", int(f.read().strip()))
        except (OSError, ValueError):
            return None

    def stop(self, grace: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                self.kill()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


class ProcessLauncher:
    """Spawn replica worker processes from a ``python -c`` source
    template taking ``(rank, root)`` argv. Each respawn is stamped with
    ``HVD_TPU_FLEET_RESTART=<attempt>`` — the fault plan's
    ``crash_loop`` kind and ``restart=`` field key to it."""

    def __init__(self, worker_src: str, root: str,
                 env: Optional[Dict[str, str]] = None):
        self.worker_src = worker_src
        self.root = root
        self.env = dict(env if env is not None else os.environ)

    def __call__(self, name: str, rank: int, attempt: int,
                 role: str = "both") -> ProcessReplica:
        env = dict(self.env, HVD_TPU_FLEET_RESTART=str(attempt),
                   HOROVOD_SERVE_ROLE=str(role))
        proc = subprocess.Popen(
            [sys.executable, "-c", self.worker_src, str(rank), self.root],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        return ProcessReplica(proc, self.root, rank, attempt)


# ---------------------------------------------------------------------------
# slot record
# ---------------------------------------------------------------------------

class ReplicaSlot:
    """One supervised replica: identity (name/rank), the live process
    handle, lifecycle state, and the death/restart bookkeeping the
    crash-loop detector reads."""

    def __init__(self, name: str, rank: int, role: str,
                 serve_role: str = "both"):
        self.name = name
        self.rank = int(rank)
        self.role = role               # "serving" | "spare"
        self.serve_role = serve_role   # "prefill" | "decode" | "both"
        self.state = STARTING
        self.handle: Any = None
        self.attempt = 0
        self.address: Optional[Tuple[str, int]] = None
        self.client: Optional[RemoteClient] = None
        self.restarts = 0
        self.metrics_port = 0          # from the status RPC, per attempt
        self.deaths: Deque[float] = deque()
        self.probe_failures = 0
        self.next_restart_at = 0.0
        self.quarantine_reason: Optional[str] = None
        self.died_at: Optional[float] = None
        self.rolling = False           # under rolling_restart control

    def display_state(self) -> str:
        if self.state == LIVE and self.role == "spare":
            return SPARE
        return self.state

    def describe(self) -> Dict[str, Any]:
        return {"name": self.name, "rank": self.rank, "role": self.role,
                "serve_role": self.serve_role,
                "state": self.display_state(), "attempt": self.attempt,
                "restarts": self.restarts,
                "quarantine_reason": self.quarantine_reason,
                "address": self.address}


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

class FleetSupervisor:
    """Hold a serving fleet at its target size.

    ``launcher(name, rank, attempt)`` must return a handle with
    ``alive()``, ``address() -> (host, port) | None``, ``stop()``, and
    ``kill()`` — :class:`ProcessLauncher` for real processes, anything
    duck-typed for tests. Knob defaults resolve from the
    ``HOROVOD_SERVE_FLEET_*`` family in ``config.py``."""

    def __init__(self, launcher: Callable[[str, int, int], Any],
                 target: int, *, spares: Optional[int] = None,
                 prefill: Optional[int] = None,
                 prefill_spares: Optional[int] = None,
                 membership_path: Optional[str] = None,
                 probe_seconds: Optional[float] = None,
                 restart_budget: Optional[int] = None,
                 backoff_seconds: Optional[float] = None,
                 backoff_cap_seconds: Optional[float] = None,
                 crash_loop_k: Optional[int] = None,
                 crash_loop_window_seconds: Optional[float] = None,
                 unreachable_probes: int = 3,
                 probe_rpc_timeout: float = 1.0,
                 rng: Optional[random.Random] = None):
        from horovod_tpu.config import get_config
        cfg = get_config()
        if target < 1:
            raise ValueError(f"fleet target must be >= 1, got {target}")
        self.launcher = launcher
        self.target = int(target)
        self.spares = int(cfg.serve_fleet_spares if spares is None
                          else spares)
        self.prefill = int(cfg.serve_fleet_prefill if prefill is None
                           else prefill)
        self.prefill_spares = int(cfg.serve_fleet_prefill_spares
                                  if prefill_spares is None
                                  else prefill_spares)
        if self.prefill >= self.target and self.prefill > 0:
            raise ValueError(
                f"prefill pool ({self.prefill}) must leave at least one "
                f"decode replica (target={self.target}); set "
                "HOROVOD_SERVE_FLEET_PREFILL below the fleet target")
        if self.prefill_spares > self.spares:
            raise ValueError(
                f"prefill spares ({self.prefill_spares}) exceed total "
                f"spares ({self.spares}); raise "
                "HOROVOD_SERVE_FLEET_SPARES or lower "
                "HOROVOD_SERVE_FLEET_PREFILL_SPARES")
        self.membership_path = membership_path
        self.probe_s = float(cfg.serve_fleet_probe_seconds
                             if probe_seconds is None else probe_seconds)
        # An explicit probe_seconds pins the poll period; otherwise the
        # config-bus subscriber (start()) re-reads the knob on mutation.
        self._probe_pinned = probe_seconds is not None
        self._confbus_sub: Optional[Callable] = None
        self.restart_budget = int(cfg.serve_fleet_restart_budget
                                  if restart_budget is None
                                  else restart_budget)
        self.backoff_s = float(cfg.serve_fleet_backoff_seconds
                               if backoff_seconds is None
                               else backoff_seconds)
        self.backoff_cap_s = float(cfg.serve_fleet_backoff_cap_seconds
                                   if backoff_cap_seconds is None
                                   else backoff_cap_seconds)
        self.crash_loop_k = int(cfg.serve_fleet_crash_loop_k
                                if crash_loop_k is None else crash_loop_k)
        self.crash_loop_window_s = float(
            cfg.serve_fleet_crash_loop_window_seconds
            if crash_loop_window_seconds is None
            else crash_loop_window_seconds)
        self.unreachable_probes = int(unreachable_probes)
        self.probe_rpc_timeout = float(probe_rpc_timeout)
        self._rng = rng or random.Random()
        # With a prefill pool carved out, the first `prefill` serving
        # ranks prefill and the rest decode; a monolithic fleet
        # (prefill=0) keeps every replica "both". Spares mirror the
        # split: the first `prefill_spares` heal the prefill pool, the
        # rest the decode pool — promotion is same-pool only, so a
        # decode death can never silently shrink prefill capacity.
        def _serving_role(i: int) -> str:
            if self.prefill <= 0:
                return "both"
            return "prefill" if i < self.prefill else "decode"

        def _spare_role(i: int) -> str:
            if self.prefill <= 0:
                return "both"
            return ("prefill" if i < self.prefill_spares else "decode")

        self._slots: List[ReplicaSlot] = []
        for i in range(self.target):
            self._slots.append(
                ReplicaSlot(f"r{i}", i, "serving",
                            serve_role=_serving_role(i)))
        for i in range(self.spares):
            self._slots.append(
                ReplicaSlot(f"s{i}", self.target + i, "spare",
                            serve_role=_spare_role(i)))
        import inspect
        try:
            params = inspect.signature(self.launcher).parameters
            self._launcher_takes_role = (
                "role" in params
                or any(p.kind == inspect.Parameter.VAR_KEYWORD
                       for p in params.values()))
        except (TypeError, ValueError):
            self._launcher_takes_role = False
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._member_version = 0
        self._members: Dict[str, Dict[str, Any]] = {}
        self._metrics_srv: Optional[Any] = None
        metrics.gauge("fleet_target_replicas").set(float(self.target))

    # -- membership file --------------------------------------------------

    def _publish_membership(self) -> None:
        if self.membership_path is None:
            return
        with self._lock:
            self._member_version += 1
            doc = {"version": self._member_version,
                   "replicas": sorted(self._members.values(),
                                      key=lambda r: r["name"])}
        # The dispatcher state bus gossips per-replica health through a
        # ``health`` block in this same file — carry it forward so an
        # atomic membership rewrite never erases what the frontends have
        # learned about replica liveness.
        try:
            with open(self.membership_path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) \
                    and isinstance(prev.get("health"), dict):
                doc["health"] = prev["health"]
        except (OSError, ValueError):
            pass
        tmp = f"{self.membership_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, self.membership_path)

    def _member_add(self, slot: ReplicaSlot) -> None:
        if slot.address is None:
            return
        with self._lock:
            # metrics_port rides the membership entry so the health
            # plane's FleetCollector can scrape every member without a
            # second discovery channel; attempt re-keys the scraped
            # series, keeping windowed rates reset-safe across respawns.
            self._members[slot.name] = {
                "name": slot.name, "host": slot.address[0],
                "port": slot.address[1], "attempt": slot.attempt,
                "metrics_port": slot.metrics_port,
                "role": slot.serve_role}
        self._publish_membership()

    def _member_remove(self, slot: ReplicaSlot) -> None:
        with self._lock:
            removed = self._members.pop(slot.name, None)
        if removed is not None:
            self._publish_membership()

    # -- lifecycle --------------------------------------------------------

    def start(self, wait_live_s: Optional[float] = None) -> \
            "FleetSupervisor":
        """Launch every slot (serving + spares) and start the
        supervision thread. With ``wait_live_s``, block until the
        serving target is fully live (raises on timeout)."""
        for slot in self._slots:
            self._launch(slot)
            # Pre-register the per-slot quarantine event counter at zero:
            # a counter series born by its FIRST inc has no baseline
            # sample, so a windowed reset-aware delta over it reads 0 —
            # the zero point makes the first quarantine visible to the
            # health plane's availability window.
            metrics.counter("fleet_quarantines_total", replica=slot.name)
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="start", target=self.target,
                                 spares=self.spares)
        self._start_metrics_http()
        if self._confbus_sub is None and not self._probe_pinned:
            # Re-read the probe period when the config bus mutates it —
            # the _run loop waits `self.probe_s` per tick, so the new
            # cadence takes effect on the next sweep.
            def _on_knob(env, old, new, ep):
                if env == "HOROVOD_SERVE_FLEET_PROBE":
                    self.probe_s = float(new)
            try:
                from horovod_tpu import confbus
                self._confbus_sub = confbus.subscribe(_on_knob)
            except Exception:
                self._confbus_sub = None
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="hvd-fleet", daemon=True)
            self._thread.start()
        if wait_live_s is not None:
            deadline = time.monotonic() + float(wait_live_s)
            while time.monotonic() < deadline:
                if self.live_serving_count() >= self.target:
                    return self
                time.sleep(0.05)
            raise TimeoutError(
                f"fleet not live after {wait_live_s:g}s: "
                f"{[s.describe() for s in self._slots]}")
        return self

    def _start_metrics_http(self) -> None:
        """Expose the supervisor's registry over HTTP when
        ``HOROVOD_METRICS_PORT`` is set. Replica servers claim
        ``base + rank``, so the supervisor scans upward from the base
        for a free port rather than colliding with rank 0."""
        from horovod_tpu.config import get_config
        base = get_config().metrics_port
        if base == 0 or self._metrics_srv is not None:
            return
        try:
            if base < 0:                  # =auto: ephemeral bind
                self._metrics_srv = metrics.metrics_http(0)
            else:
                self._metrics_srv = metrics.metrics_http(base,
                                                         fallback_ports=32)
        except OSError as exc:
            logger = metrics.logger if hasattr(metrics, "logger") else None
            if logger is not None:
                logger.warning("fleet: metrics endpoint unavailable: %s",
                               exc)

    def stop(self) -> None:
        self._stop.set()
        if self._confbus_sub is not None:
            try:
                from horovod_tpu import confbus
                confbus.unsubscribe(self._confbus_sub)
            except Exception:
                pass
            self._confbus_sub = None
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        for slot in self._slots:
            if slot.handle is not None:
                try:
                    slot.handle.stop()
                except Exception:
                    pass
        if self._metrics_srv is not None:
            try:
                self._metrics_srv.stop()
            except Exception:
                pass
            self._metrics_srv = None
        metrics._timeline_marker("FLEET", category="fleet", event="stop")

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception:   # noqa: BLE001 — supervision must survive
                pass
            self._stop.wait(self.probe_s)

    # -- introspection ----------------------------------------------------

    def slot(self, name: str) -> ReplicaSlot:
        for s in self._slots:
            if s.name == name:
                return s
        raise KeyError(name)

    def slots(self) -> List[ReplicaSlot]:
        return list(self._slots)

    def live_serving_count(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots
                       if s.role == "serving" and s.state == LIVE)

    def status(self) -> Dict[str, Any]:
        with self._lock:
            return {"target": self.target,
                    "live": self.live_serving_count(),
                    "slots": [s.describe() for s in self._slots]}

    def apply_config(self, name: str, value: Any, *,
                     reason: str = "") -> Dict[str, Any]:
        """Fan one config-bus mutation out fleet-wide: apply locally
        via ``confbus.set_config`` (the supervisor's own ledger/epoch),
        then push the same mutation over the auth-gated ``set_config``
        RPC to every live serving replica. A local refusal/rejection
        stops the fan-out — the fleet never diverges on a knob the bus
        won't accept. Any member failure is itself a ledger entry plus
        ``config_mutations_total{knob,outcome=partial}`` so drift is
        observable (the ``hvd.top`` CFG column shows which replica
        missed it); returns ``{result, applied, failed, epoch}``."""
        from horovod_tpu import confbus
        local = confbus.set_config(name, value, reason=reason,
                                   origin="fleet")
        if not local.get("ok"):
            return {"result": local, "applied": [], "failed": [],
                    "epoch": local.get("epoch")}
        with self._lock:
            targets = [(s.name, s.client) for s in self._slots
                       if s.role == "serving" and s.state == LIVE
                       and s.client is not None]
        applied, failed = [], []
        for rep, client in targets:
            try:
                res = client.set_config(name, value, reason=reason)
                sub = res.get("result", {}) if isinstance(res, dict) else {}
                if sub.get("ok"):
                    applied.append(rep)
                else:
                    failed.append(rep)
            except TransportError:
                failed.append(rep)
        if failed:
            knob = local.get("knob", str(name))
            metrics.counter("config_mutations_total", knob=knob,
                            outcome="partial").inc()
            confbus._append_ledger(
                {"ts": time.time(), "event": "fanout", "knob": knob,
                 "outcome": "partial", "applied": applied,
                 "failed": failed, "epoch": local.get("epoch"),
                 "who": f"fleet:pid{os.getpid()}", "reason": reason})
            _note_fleet("config_fanout_partial", knob=knob,
                        failed=failed)
        return {"result": local, "applied": applied, "failed": failed,
                "epoch": local.get("epoch")}

    # -- supervision ------------------------------------------------------

    def _launch(self, slot: ReplicaSlot) -> None:
        if self._launcher_takes_role:
            slot.handle = self.launcher(slot.name, slot.rank,
                                        slot.attempt,
                                        role=slot.serve_role)
        else:
            slot.handle = self.launcher(slot.name, slot.rank,
                                        slot.attempt)
        slot.state = STARTING if slot.restarts == 0 else RESTARTING
        slot.address = None
        slot.client = None
        slot.probe_failures = 0

    def _backoff(self, slot: ReplicaSlot) -> float:
        # Jittered exponential per slot: full-jitter draw at the ceiling
        # 2^(restarts-1) * base, capped.
        d = min(self.backoff_cap_s,
                self.backoff_s * (2.0 ** max(0, slot.restarts - 1)))
        return self._rng.uniform(d / 2.0, d)

    def poll_once(self) -> None:
        """One supervision sweep: respawn due slots, detect deaths
        (process exit or ``unreachable_probes`` consecutive failed
        health RPCs), admit freshly-ready replicas, refresh gauges.
        Normally driven by the background thread; tests call it
        directly."""
        now = time.monotonic()
        for slot in self._slots:
            if slot.rolling or slot.state == QUARANTINED:
                continue
            if slot.handle is None:
                if now >= slot.next_restart_at:
                    self._launch(slot)
                continue
            if not slot.handle.alive():
                self._on_death(slot, "exit")
                continue
            if slot.address is None:
                addr = slot.handle.address()
                if addr is None:
                    continue
                slot.address = addr
                slot.client = RemoteClient(
                    addr, name=slot.name, max_retries=0,
                    rpc_timeout=self.probe_rpc_timeout)
            self._probe(slot)
        self._update_gauges()

    def _probe(self, slot: ReplicaSlot) -> None:
        try:
            st = slot.client.status(retry=False)
        except TransportError:
            slot.probe_failures += 1
            if slot.state == LIVE \
                    and slot.probe_failures >= self.unreachable_probes:
                # Alive as a process but dark on the network (partition,
                # wedged listener): indistinguishable from dead for
                # serving purposes — replace it.
                self._on_death(slot, "unreachable")
            return
        slot.probe_failures = 0
        try:
            slot.metrics_port = int(st.get("metrics_port", 0) or 0)
        except (TypeError, ValueError):
            slot.metrics_port = 0
        if st.get("alive", False) and slot.state != LIVE:
            self._admit(slot)

    def _admit(self, slot: ReplicaSlot) -> None:
        was = slot.state
        slot.state = LIVE
        if slot.role == "serving":
            self._member_add(slot)
        else:
            self._heal_quarantined()
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="live", replica=slot.name,
                                 attempt=slot.attempt, was=was)
        _note_fleet("live", replica=slot.name, attempt=slot.attempt,
                    was=was)
        # refresh gauges at the transition, not just on the next poll
        # tick — rolling_restart returns the instant the last replica
        # is admitted, and callers snapshot right away (the stream
        # wire's push delivery removed the poll-cycle slack that used
        # to hide this staleness)
        self._update_gauges()

    def _request_dump(self, slot: ReplicaSlot, reason: str) -> None:
        """Best-effort pre-kill forensics: ask the replica to publish
        its flight-recorder bundle over the ``dump`` RPC before we
        destroy the process (blackbox.py; no-op replies when the
        replica runs without HOROVOD_BLACKBOX)."""
        if slot.client is None:
            return
        try:
            slot.client.dump(label=slot.name, note=reason)
        except TransportError:
            pass            # dead or dark: its own death path dumped

    def _on_death(self, slot: ReplicaSlot, reason: str) -> None:
        if slot.rolling:
            return     # rolling_restart owns this slot's stop/respawn
        now = time.monotonic()
        slot.died_at = now
        if reason != "exit":
            # Alive-but-dark (unreachable): one dump attempt before the
            # kill — an exit()ed process has nobody left to answer.
            self._request_dump(slot, reason)
        if slot.handle is not None:
            try:
                slot.handle.kill()
            except Exception:
                pass
        slot.handle = None
        slot.address = None
        slot.client = None
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="death", replica=slot.name,
                                 reason=reason, attempt=slot.attempt)
        _note_fleet("death", replica=slot.name, reason=reason,
                    attempt=slot.attempt)
        was_serving = slot.role == "serving" and slot.state == LIVE
        slot.state = RESTARTING
        self._member_remove(slot)
        if was_serving:
            self._promote_spare(slot)
        slot.deaths.append(now)
        while slot.deaths and now - slot.deaths[0] > self.crash_loop_window_s:
            slot.deaths.popleft()
        if len(slot.deaths) >= self.crash_loop_k:
            self._quarantine(
                slot, f"crash_loop: {len(slot.deaths)} deaths in "
                f"{self.crash_loop_window_s:g}s window")
            return
        if slot.restarts >= self.restart_budget:
            self._quarantine(
                slot, f"restart budget exhausted "
                f"({self.restart_budget} restarts)")
            return
        slot.restarts += 1
        slot.attempt += 1
        slot.next_restart_at = now + self._backoff(slot)
        metrics.counter("fleet_restarts_total", replica=slot.name,
                        reason=reason).inc()
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="restart_scheduled",
                                 replica=slot.name, reason=reason,
                                 attempt=slot.attempt,
                                 in_seconds=slot.next_restart_at - now)

    def _promote_spare(self, dead: ReplicaSlot) -> None:
        """Move a warm spare into the dead rank's serving slot: the
        spare's engine is already compiled and its server listening, so
        promotion is a membership write, not a process spawn. The dead
        slot rebuilds in the background as the new spare."""
        t0 = time.monotonic()
        # Same-pool first: a dead prefill replica must be healed by a
        # prefill-warmed spare (and decode by decode) so the split the
        # dispatcher routes by survives the promotion; a "both" spare
        # can stand in anywhere as a last resort.
        ranked = [s for s in self._slots
                  if s.role == "spare" and s.state == LIVE
                  and s.serve_role == dead.serve_role]
        ranked += [s for s in self._slots
                   if s.role == "spare" and s.state == LIVE
                   and s.serve_role == "both"
                   and s.serve_role != dead.serve_role]
        for spare in ranked:
            spare.role, dead.role = "serving", "spare"
            self._member_add(spare)
            dt = time.monotonic() - t0
            metrics.histogram("fleet_promotion_seconds").observe(dt)
            metrics._timeline_marker(
                "FLEET", category="fleet", event="promote",
                spare=spare.name, into=dead.name,
                pool=spare.serve_role, seconds=dt)
            _note_fleet("promote", spare=spare.name, into=dead.name,
                        pool=spare.serve_role)
            return

    def _heal_quarantined(self) -> None:
        """A parked serving slot never comes back, so a warm spare takes
        its place whenever one is live: at the quarantine, or when the
        spare is admitted later. ``_on_death`` promotes only at the death
        of a LIVE serving replica; one that is parked while restarting
        (or while the spare is itself rebuilding) would otherwise leave
        the fleet under its target beside an idle spare."""
        for slot in self._slots:
            if slot.role == "serving" and slot.state == QUARANTINED:
                self._promote_spare(slot)

    def _quarantine(self, slot: ReplicaSlot, reason: str) -> None:
        slot.state = QUARANTINED
        slot.quarantine_reason = reason
        slot.next_restart_at = float("inf")
        self._heal_quarantined()
        # Event counter next to the sticky state gauge: the continuous
        # doctor's windowed availability check alerts on the *event*
        # (which ages out of the window and clears) rather than the
        # quarantined-replicas gauge (which stays up by design).
        metrics.counter("fleet_quarantines_total",
                        replica=slot.name).inc()
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="quarantine", replica=slot.name,
                                 reason=reason)
        _note_fleet("quarantine", replica=slot.name, reason=reason)
        # Parking a replica is the supervisor's strongest diagnosis —
        # fold every bundle published so far (the quarantined replica's
        # crash-time dumps included; workers share HOROVOD_BLACKBOX_DIR)
        # into one fleet bundle next to them.
        self.collect_postmortems(label=f"fleet-{slot.name}", reason=reason)
        self._update_gauges()

    def collect_postmortems(self, label: str = "fleet",
                            reason: str = "") -> Optional[str]:
        """Gather the per-replica ``postmortem-*`` bundles from the
        shared blackbox dir into one ``postmortem-<label>-<ts>/`` fleet
        bundle whose ``fleet.json`` records every slot's state — the one
        artifact to grab after a bad episode. No-op (``None``) unless
        this process runs with ``HOROVOD_BLACKBOX``."""
        try:
            from horovod_tpu import blackbox
            rec = blackbox.ensure()
            if rec is None:
                return None
            with self._lock:
                slots = [{"replica": s.name, "state": s.display_state(),
                          "role": s.role, "attempt": s.attempt,
                          "restarts": s.restarts,
                          "quarantine_reason": s.quarantine_reason}
                         for s in self._slots]
            # Snapshot the member bundles BEFORE dumping our own (the
            # supervisor bundle lands beside the copies, not inside).
            members = [b for b in blackbox.find_bundles(rec.root)
                       if "-fleet" not in os.path.basename(b)]
            bundle = rec.dump(trigger="fleet", label=label, note=reason)
            if bundle is None:
                return None
            with open(os.path.join(bundle, "fleet.json"), "w") as f:
                json.dump({"reason": reason, "slots": slots,
                           "members": [os.path.basename(b)
                                       for b in members]}, f)
            import shutil
            for b in members:
                dst = os.path.join(bundle, os.path.basename(b))
                try:
                    shutil.copytree(b, dst)
                except OSError:
                    continue
            return bundle
        except Exception:
            return None

    def _update_gauges(self) -> None:
        counts = {LIVE: 0, STARTING: 0, RESTARTING: 0, QUARANTINED: 0,
                  SPARE: 0}
        by_role: Dict[Tuple[str, str], int] = {}
        with self._lock:
            for slot in self._slots:
                st = slot.display_state()
                counts[st] = counts.get(st, 0) + 1
                key = (slot.serve_role, st)
                by_role[key] = by_role.get(key, 0) + 1
        for state, n in counts.items():
            metrics.gauge("fleet_replicas", state=state).set(float(n))
        # Per-pool capacity for the health plane and hvd.top: a
        # disaggregated fleet is healthy only when BOTH pools hold
        # their share of the target.
        for role in ("prefill", "decode", "both"):
            for state in (LIVE, STARTING, RESTARTING, QUARANTINED,
                          SPARE):
                metrics.gauge("fleet_role_replicas", role=role,
                              state=state).set(
                    float(by_role.get((role, state), 0)))

    # -- rolling restart --------------------------------------------------

    def rolling_restart(self, *, drain_timeout: float = 60.0,
                        ready_timeout: float = 120.0) -> Dict[str, Any]:
        """Drain + restart every live serving replica, one at a time.

        Per replica: withdraw it from membership (the dispatcher stops
        placing new work; its in-flight handles keep polling), issue
        the ``drain`` RPC (queued/active requests finish; new submits
        bounce retryable and re-place elsewhere), wait for the load to
        hit zero, stop the process, respawn it at ``attempt+1``, wait
        for readmission (fresh breaker CLOSED, status healthy), then
        move to the next. Bounded unavailability: at most one replica
        out at any moment, zero dropped requests."""
        t_all = time.monotonic()
        restarted: List[str] = []
        with self._lock:
            todo = [s for s in self._slots
                    if s.role == "serving" and s.state == LIVE]
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="rolling_restart_begin",
                                 replicas=len(todo))
        for slot in todo:
            t0 = time.monotonic()
            slot.rolling = True
            try:
                self._roll_one(slot, drain_timeout, ready_timeout)
            finally:
                slot.rolling = False
            dt = time.monotonic() - t0
            metrics.histogram("rolling_restart_seconds").observe(dt)
            metrics.counter("fleet_restarts_total", replica=slot.name,
                            reason="rolling").inc()
            restarted.append(slot.name)
        metrics._timeline_marker("FLEET", category="fleet",
                                 event="rolling_restart_done",
                                 replicas=len(restarted),
                                 seconds=time.monotonic() - t_all)
        return {"restarted": restarted,
                "seconds": time.monotonic() - t_all}

    def _roll_one(self, slot: ReplicaSlot, drain_timeout: float,
                  ready_timeout: float) -> None:
        self._member_remove(slot)
        try:
            slot.client.drain(timeout=drain_timeout)
        except TransportError:
            pass                       # dead already: respawn heals it
        deadline = time.monotonic() + drain_timeout
        while time.monotonic() < deadline:
            try:
                st = slot.client.status(retry=False)
                if int(st.get("load", 0)) <= 0:
                    break
            except TransportError:
                break                  # unreachable: nothing to wait on
            time.sleep(min(0.1, self.probe_s))
        # Forensics before the stop, same as before a kill: a rolling
        # restart that later turns out to have masked a real failure
        # still left a bundle to audit.
        self._request_dump(slot, "rolling_restart")
        if slot.handle is not None:
            try:
                slot.handle.stop()
            except Exception:
                pass
        slot.attempt += 1
        self._launch(slot)
        slot.state = RESTARTING
        deadline = time.monotonic() + ready_timeout
        while time.monotonic() < deadline:
            if slot.address is None:
                addr = slot.handle.address()
                if addr is not None:
                    slot.address = addr
                    slot.client = RemoteClient(
                        addr, name=slot.name, max_retries=0,
                        rpc_timeout=self.probe_rpc_timeout)
            else:
                try:
                    if slot.client.status(retry=False).get("alive"):
                        self._admit(slot)
                        return
                except TransportError:
                    pass
            time.sleep(min(0.1, self.probe_s))
        raise TimeoutError(
            f"rolling restart: {slot.name} not ready after "
            f"{ready_timeout:g}s")

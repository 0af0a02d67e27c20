"""Process supervision for the multi-process pod launcher.

Every distributed subsystem since PR 9 — serving fleet, GRPO flywheel,
elastic PBT, telemetry plane, executable store — already exchanges ALL
state through commit-dir stores on a shared filesystem root. This module
supplies the missing half of the Podracer/Sebulba deployment story: the
machinery to run each pod as a **real OS process** and supervise it.

Three layers:

- **Role harness** (``python -m agilerl_tpu.resilience.proc <spec.json>``):
  the child-side driver. It installs a :class:`~agilerl_tpu.resilience
  .preemption.PreemptionGuard` FIRST (so even a SIGTERM during JAX import
  drains cleanly), beats a :class:`~agilerl_tpu.resilience.membership
  .HeartbeatStore` lease tagged with the role, resolves the spec's
  ``module:function`` entry point to build the role object, then runs the
  poll-cadence tick loop. Exit is always through a final telemetry flush +
  an atomic status file: ``done`` (tick returned complete), ``preempted``
  (guard latched — final drain ran), or ``crashed`` (exception, traceback
  recorded). Exit codes mirror the states so the supervisor never needs to
  parse a status file to decide on a restart.

- **:class:`SupervisedProcess`**: one spawned role. Children run in their
  OWN session (``start_new_session=True``) so the launcher can signal the
  child's whole process group without ever signalling itself; termination
  is deliberately **double-delivered** (group signal + direct signal) —
  the PreemptionGuard latch is idempotent, and double delivery is exactly
  what a real pod sees when an external preemption notice races the
  launcher's forward.

- **:class:`ProcessSupervisor`**: the fleet of children over one
  filesystem root. ``poll()`` reaps exits, restarts crashed roles with a
  bumped incarnation (bounded by ``max_restarts``), and
  ``shutdown()`` drains every child through SIGTERM within a grace window
  before escalating to SIGKILL — then verifies nothing is left running
  (the no-orphans contract).

Nothing here touches pod payloads: weights, trajectories, KV pages,
telemetry, and executables keep flowing through the existing stores. The
supervisor only moves **signals, liveness, and exit status**.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import signal
import subprocess
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional, Union

from agilerl_tpu.resilience.atomic import atomic_write_bytes
from agilerl_tpu.resilience.membership import HeartbeatStore, pid_alive
from agilerl_tpu.resilience.preemption import PreemptionGuard

#: harness exit codes — the supervisor's restart policy keys off these
EXIT_DONE = 0        #: role tick loop reported completion
EXIT_CRASH = 1       #: unhandled exception (restartable)
EXIT_PREEMPTED = 3   #: guard latched; drained gracefully (NOT restartable)
EXIT_ESCALATED = 130  #: double ^C — immediate stop, no drain

#: root-relative layout the launcher and every role agree on
SPECS_DIR = "specs"
STATUS_DIR = "status"
LOGS_DIR = "logs"
MEMBERSHIP_DIR = "membership"
TELEMETRY_DIR = "telemetry"


@dataclasses.dataclass
class RoleSpec:
    """Everything a child process needs to run one role, JSON-round-trip
    (the spec file IS the process's argv). ``target`` is a
    ``module:function`` entry point called with the :class:`RoleContext`;
    it returns either an object with ``tick()`` (optional ``drain()``) or
    a bare zero-arg tick callable. ``kwargs`` must be JSON-able — object
    graphs are rebuilt child-side from entry points, never pickled across
    the exec boundary. ``env`` must name ``JAX_PLATFORMS``: an accelerator
    belongs to one process at a time, so each role is TOLD its backend —
    on one chip one role owns it and the others say ``cpu`` — and a role
    never inherits it by accident (:meth:`SupervisedProcess.spawn`
    refuses a spec that does not say)."""

    name: str
    target: str
    root: str
    member_id: int
    kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    replica: int = 0
    incarnation: int = 0
    lease_timeout: float = 5.0
    beat_interval: Optional[float] = None  # default: lease_timeout / 4
    poll_interval: float = 0.0
    env: Dict[str, str] = dataclasses.field(default_factory=dict)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, text: str) -> "RoleSpec":
        data = json.loads(text)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in data.items() if k in known})


class RoleContext:
    """The harness-side plumbing handed to a role's entry point: the spec,
    the shared root, the lease store (already beating), the preemption
    guard, and the process registry. Roles read ``should_stop`` at their
    own step boundaries when one tick spans multiple store interactions."""

    def __init__(self, spec: RoleSpec, root: Path,
                 heartbeat: HeartbeatStore, guard: PreemptionGuard,
                 metrics) -> None:
        self.spec = spec
        self.root = root
        self.heartbeat = heartbeat
        self.guard = guard
        self.metrics = metrics

    @property
    def should_stop(self) -> bool:
        return self.guard.requested


def resolve_target(target: str):
    """``module:function`` -> the callable (no eval, no pickling)."""
    mod, sep, fn = target.partition(":")
    if not sep or not mod or not fn:
        raise ValueError(
            f"role target must be 'module:function', got {target!r}")
    return getattr(importlib.import_module(mod), fn)


def _status_path(root: Path, name: str) -> Path:
    return root / STATUS_DIR / f"{name}.json"


def _write_status(root: Path, spec: RoleSpec, state: str,
                  ticks: int = 0, error: Optional[str] = None) -> None:
    payload = {
        "role": spec.name,
        "pid": os.getpid(),
        "incarnation": int(spec.incarnation),
        "state": state,
        "ticks": int(ticks),
        "time": time.time(),
    }
    if error:
        payload["error"] = error
    atomic_write_bytes(_status_path(root, spec.name),
                       json.dumps(payload, indent=2).encode())


def read_statuses(root: Union[str, Path]) -> Dict[str, Dict[str, Any]]:
    """All readable role status files under ``root`` (atomic writes mean
    an unreadable one is external damage, not a crash artifact)."""
    out: Dict[str, Dict[str, Any]] = {}
    status_dir = Path(root) / STATUS_DIR
    if not status_dir.is_dir():
        return out
    for p in sorted(status_dir.glob("*.json")):
        try:
            out[p.stem] = json.loads(p.read_text())
        except (OSError, ValueError):
            continue
    return out


def run_role(spec_path: Union[str, Path]) -> int:
    """Child-side harness: guard -> lease -> build role -> tick loop ->
    drain -> status. Returns the process exit code (see ``EXIT_*``)."""
    spec = RoleSpec.from_json(Path(spec_path).read_text())
    root = Path(spec.root)

    # the guard comes FIRST: a SIGTERM that lands during the (seconds-long)
    # package/JAX import must latch, not kill us mid-initialisation. The
    # harness owns the outer handlers; any loop-level guard a role installs
    # later chains back to these (preemption.py's supervised-children fix).
    guard = PreemptionGuard().install()

    from agilerl_tpu import observability

    reg = observability.get_registry()
    sink_path = root / LOGS_DIR / f"{spec.name}.events.jsonl"
    sink_path.parent.mkdir(parents=True, exist_ok=True)
    reg.attach_sink(observability.JsonlSink(str(sink_path)))
    guard._registry = reg  # deferred preemption record lands in OUR sink

    heartbeat = HeartbeatStore(root / MEMBERSHIP_DIR,
                               lease_timeout=spec.lease_timeout,
                               registry=reg)
    meta = {"role": spec.name, "replica": int(spec.replica)}
    heartbeat.beat(spec.member_id, spec.incarnation, meta=meta)
    _write_status(root, spec, "running")

    publisher = observability.TelemetryPublisher(
        root / TELEMETRY_DIR, spec.name, reg,
        interval_s=max(spec.lease_timeout / 2.0, 0.25), metrics=reg)

    beat_interval = (spec.beat_interval if spec.beat_interval is not None
                     else spec.lease_timeout / 4.0)
    ctx = RoleContext(spec, root, heartbeat, guard, reg)
    ticks = 0
    state, code, error = "done", EXIT_DONE, None
    try:
        role = resolve_target(spec.target)(ctx)
        tick = role if callable(role) and not hasattr(role, "tick") \
            else role.tick
        drain = getattr(role, "drain", None)
        last_beat = time.monotonic()
        while True:
            if guard.requested:
                state, code = "preempted", EXIT_PREEMPTED
                break
            done = tick()
            ticks += 1
            now = time.monotonic()
            if now - last_beat >= beat_interval:
                heartbeat.beat(spec.member_id, spec.incarnation, meta=meta)
                last_beat = now
            publisher.publish()  # self-throttled by interval_s
            if done:
                break
            if spec.poll_interval > 0:
                time.sleep(spec.poll_interval)
        # graceful paths drain: the role's final snapshot/flush hook runs
        # for completion AND preemption (the guard's grace window)
        if callable(drain):
            drain()
    except KeyboardInterrupt:
        # double ^C escalation: the user means NOW — no drain
        state, code, error = "escalated", EXIT_ESCALATED, "KeyboardInterrupt"
    except Exception:
        state, code = "crashed", EXIT_CRASH
        error = traceback.format_exc()
    finally:
        try:
            publisher.publish(force=True)
        except Exception:
            pass
        if state in ("done", "preempted"):
            # graceful exits tombstone the lease so observers drop us
            # immediately; a crash leaves the stale lease for the pid
            # probe / lease timeout to surface — truthful failure telemetry
            heartbeat.mark_dead(spec.member_id)
        _write_status(root, spec, state, ticks=ticks, error=error)
        flush = getattr(getattr(reg, "sink", None), "flush", None)
        if callable(flush):
            try:
                flush()
            except Exception:
                pass
    return code


#: child argv — an import (not ``-m``) so runpy never executes a second
#: __main__ copy of this module inside the child
_CHILD_CMD = ("import sys; from agilerl_tpu.resilience.proc import "
              "run_role; sys.exit(run_role(sys.argv[1]))")


class SupervisedProcess:
    """One spawned role: the Popen handle plus the signal plumbing.

    The child gets its OWN session/process group, so group-wide signals
    from the supervisor can never loop back into the launcher, and any
    grandchildren the role spawns die with it on escalation."""

    def __init__(self, spec: RoleSpec, popen: subprocess.Popen,
                 spec_path: Path, log_path: Path) -> None:
        self.spec = spec
        self.popen = popen
        self.spec_path = spec_path
        self.log_path = log_path

    @classmethod
    def spawn(cls, spec: RoleSpec,
              extra_env: Optional[Dict[str, str]] = None
              ) -> "SupervisedProcess":
        if not (spec.env or {}).get("JAX_PLATFORMS"):
            raise ValueError(
                f"role {spec.name!r}: RoleSpec.env does not name "
                "JAX_PLATFORMS. A chip belongs to one process: say which "
                "role owns it (e.g. 'tpu') and give every other role 'cpu'")
        root = Path(spec.root)
        for sub in (SPECS_DIR, STATUS_DIR, LOGS_DIR, MEMBERSHIP_DIR,
                    TELEMETRY_DIR):
            (root / sub).mkdir(parents=True, exist_ok=True)
        spec_path = root / SPECS_DIR / \
            f"{spec.name}.{int(spec.incarnation):03d}.json"
        atomic_write_bytes(spec_path, spec.to_json().encode())
        log_path = root / LOGS_DIR / f"{spec.name}.log"
        env = dict(os.environ)
        env.update(spec.env or {})
        env.update(extra_env or {})
        # append-mode log: restarts of the same role continue one file, and
        # a torn tail line on SIGKILL is harmless
        log = open(log_path, "ab")
        try:
            popen = subprocess.Popen(
                [sys.executable, "-u", "-c", _CHILD_CMD, str(spec_path)],
                stdout=log, stderr=subprocess.STDOUT, env=env,
                start_new_session=True)
        finally:
            log.close()  # the child holds its own descriptor now
        return cls(spec, popen, spec_path, log_path)

    @property
    def pid(self) -> int:
        return self.popen.pid

    @property
    def alive(self) -> bool:
        return self.popen.poll() is None

    def poll(self) -> Optional[int]:
        return self.popen.poll()

    def wait(self, timeout: Optional[float] = None) -> Optional[int]:
        try:
            return self.popen.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None

    def _signal(self, signum: int) -> None:
        """Double delivery ON PURPOSE: the group signal covers any
        grandchildren, the direct signal covers a child that moved itself
        out of the group. The guard's latch is idempotent, and real pods
        see exactly this race (external notice + launcher forward)."""
        try:
            os.killpg(os.getpgid(self.pid), signum)
        except (ProcessLookupError, PermissionError, OSError):
            pass
        try:
            os.kill(self.pid, signum)
        except (ProcessLookupError, PermissionError, OSError):
            pass

    def terminate(self) -> None:
        self._signal(signal.SIGTERM)

    def kill(self) -> None:
        self._signal(signal.SIGKILL)


class ProcessSupervisor:
    """The launcher's fleet of supervised role processes over one root.

    ``poll()`` is the supervision step: reap exits, classify them, respawn
    crashes with a bumped incarnation (so membership reports the rejoin)
    up to ``max_restarts`` per role. ``shutdown()`` is the graceful drain:
    SIGTERM everyone, give the grace window, SIGKILL stragglers, verify no
    orphans."""

    def __init__(self, root: Union[str, Path], lease_timeout: float = 5.0,
                 grace_s: float = 10.0, max_restarts: int = 2,
                 registry=None, probe_pids: bool = True) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.lease_timeout = float(lease_timeout)
        self.grace_s = float(grace_s)
        self.max_restarts = int(max_restarts)
        self._registry_override = registry
        self.heartbeat = HeartbeatStore(
            self.root / MEMBERSHIP_DIR, lease_timeout=lease_timeout,
            registry=registry, probe_pids=probe_pids)
        self.procs: Dict[str, SupervisedProcess] = {}
        self.exits: Dict[str, int] = {}
        self.restarts: Dict[str, int] = {}
        self._shutting_down = False

    @property
    def metrics(self):
        if self._registry_override is not None:
            return self._registry_override
        from agilerl_tpu.observability import get_registry

        return get_registry()

    # -- lifecycle --------------------------------------------------------- #
    def spawn(self, spec: RoleSpec) -> SupervisedProcess:
        spec = dataclasses.replace(spec, root=str(self.root),
                                   lease_timeout=self.lease_timeout)
        proc = SupervisedProcess.spawn(spec)
        self.procs[spec.name] = proc
        self.exits.pop(spec.name, None)
        self.metrics.counter(
            "resilience/proc_spawns_total",
            help="supervised role processes spawned").inc()
        self.metrics.emit("proc_spawn", role=spec.name, pid=proc.pid,
                          incarnation=int(spec.incarnation))
        return proc

    def poll(self) -> List[Dict[str, Any]]:
        """One supervision step. Returns the exit events observed this
        call (``role``, ``code``, ``action``: done | drained | restarted |
        gave_up)."""
        events: List[Dict[str, Any]] = []
        for name, proc in list(self.procs.items()):
            if name in self.exits:
                continue
            code = proc.poll()
            if code is None:
                continue
            self.exits[name] = code
            self.metrics.counter(
                "resilience/proc_exits_total",
                help="supervised role process exits observed").inc()
            if code == EXIT_DONE:
                action = "done"
            elif code == EXIT_PREEMPTED:
                action = "drained"
            elif (not self._shutting_down
                    and self.restarts.get(name, 0) < self.max_restarts):
                self.restarts[name] = self.restarts.get(name, 0) + 1
                self.metrics.counter(
                    "resilience/proc_restarts_total",
                    help="crashed role processes respawned").inc()
                respawn = dataclasses.replace(
                    proc.spec, incarnation=proc.spec.incarnation + 1)
                self.spawn(respawn)
                action = "restarted"
            else:
                action = "gave_up"
            self.metrics.emit("proc_exit", role=name, code=code,
                              action=action)
            events.append({"role": name, "code": code, "action": action})
        return events

    def running(self) -> List[str]:
        return [n for n, p in self.procs.items()
                if n not in self.exits and p.alive]

    def all_done(self) -> bool:
        self.poll()
        return not self.running()

    def wait(self, timeout: float = 60.0,
             poll_interval: float = 0.05) -> bool:
        """Supervise until every role exits (restarts included) or the
        deadline passes. Returns True when the fleet fully drained."""
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            if self.all_done():
                return True
            time.sleep(poll_interval)
        return self.all_done()

    def statuses(self) -> Dict[str, Dict[str, Any]]:
        return read_statuses(self.root)

    # -- shutdown ---------------------------------------------------------- #
    def shutdown(self, grace_s: Optional[float] = None) -> Dict[str, Any]:
        """Graceful fleet drain: forward SIGTERM (double-delivered) to
        every live child, wait out the grace window, SIGKILL stragglers,
        reap everything, and verify no orphan survived. Returns a summary
        with per-role exit codes and the roles that needed escalation."""
        self._shutting_down = True
        grace = self.grace_s if grace_s is None else float(grace_s)
        live = [p for n, p in self.procs.items() if p.alive]
        for p in live:
            p.terminate()
        deadline = time.monotonic() + grace
        escalated: List[str] = []
        for p in live:
            remaining = deadline - time.monotonic()
            if p.wait(timeout=max(remaining, 0.01)) is None:
                escalated.append(p.spec.name)
                p.kill()
                p.wait(timeout=5.0)
        for name, p in self.procs.items():
            code = p.poll()
            if code is not None:
                self.exits[name] = code
        orphans = [p.spec.name for p in self.procs.values()
                   if pid_alive(p.pid)]
        if escalated:
            self.metrics.counter(
                "resilience/proc_escalations_total",
                help="children that outlived the SIGTERM grace window and "
                     "were SIGKILLed").inc(len(escalated))
        self.metrics.emit("proc_shutdown", exits=dict(self.exits),
                          escalated=escalated, orphans=orphans)
        return {"exits": dict(self.exits), "escalated": escalated,
                "orphans": orphans, "statuses": self.statuses()}


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(run_role(sys.argv[1]))

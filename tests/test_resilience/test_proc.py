"""Process-supervision layer (ISSUE 19): pid-probe fast failure detection,
chained PreemptionGuard handlers under double signal delivery, the role
harness + supervisor over REAL subprocesses, and genuinely concurrent
multi-process ``publish_entry`` racers on one commit directory.

These tests spawn real OS processes but only trivial roles (no GRPO
compiles) — they stay tier-1. The full multi-process flywheel runs under
the ``launch`` marker in ``tests/test_train/test_launch.py``."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from agilerl_tpu.observability import MetricsRegistry, read_jsonl
from agilerl_tpu.resilience.membership import HeartbeatStore, pid_alive
from agilerl_tpu.resilience.preemption import PreemptionGuard
from agilerl_tpu.resilience.proc import (
    EXIT_CRASH,
    EXIT_DONE,
    EXIT_PREEMPTED,
    ProcessSupervisor,
    RoleSpec,
    read_statuses,
)
from agilerl_tpu.resilience.store import (
    CorruptSnapshotError,
    committed_entries,
    read_entry,
)

pytestmark = pytest.mark.launch

REPO_ROOT = str(Path(__file__).resolve().parents[2])
_ENV = {"PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}


def _dead_pid() -> int:
    """A pid that demonstrably does not exist: spawn + reap a child."""
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    return proc.pid


# --------------------------------------------------------------------------- #
# pid probe (satellite: fast same-host failure detection)
# --------------------------------------------------------------------------- #
def test_pid_alive():
    assert pid_alive(os.getpid())
    assert not pid_alive(-1)
    assert not pid_alive(0)
    assert not pid_alive(_dead_pid())


def test_heartbeat_pid_probe_surfaces_crash_before_lease_expiry(tmp_path):
    # an ENORMOUS lease timeout: only the pid probe can surface the loss
    hb = HeartbeatStore(tmp_path, lease_timeout=10_000.0,
                        registry=MetricsRegistry())
    hb.beat(0)  # this process — alive
    hb.beat(1, pid=_dead_pid())  # fresh lease, dead local writer
    alive = hb.alive()
    assert 0 in alive and 1 not in alive

    # poll() reports the crashed member as lost immediately
    hb.expect([0, 1])
    ev = hb.poll()
    assert ev is not None and ev.lost == (1,) and 0 in ev.alive


def test_pid_probe_skips_other_nodes_and_disable(tmp_path):
    reg = MetricsRegistry()
    dead = _dead_pid()
    hb = HeartbeatStore(tmp_path, lease_timeout=10_000.0, registry=reg)
    # a lease from ANOTHER node is never probed — only its lease can age out
    hb.beat(2, pid=dead, node="some-other-host")
    assert 2 in hb.alive()
    # probe_pids=False restores pure lease-window semantics
    hb2 = HeartbeatStore(tmp_path, lease_timeout=10_000.0, registry=reg,
                         probe_pids=False)
    hb2.beat(3, pid=dead)
    assert 3 in hb2.alive()
    # and the probing store still drops it
    assert 3 not in hb.alive()


# --------------------------------------------------------------------------- #
# PreemptionGuard chaining (satellite: supervised children)
# --------------------------------------------------------------------------- #
def test_guard_chains_to_previously_installed_guard():
    reg = MetricsRegistry()
    outer = PreemptionGuard(registry=reg)
    inner = PreemptionGuard(registry=reg)
    outer.install()
    try:
        inner.install()
        try:
            signal.raise_signal(signal.SIGTERM)
            # BOTH guards latched: the inner handler chained to the outer
            assert inner.requested and outer.requested
        finally:
            inner.uninstall()
    finally:
        outer.uninstall()


def test_double_sigterm_delivery_stays_graceful():
    """Launcher forward + process-group delivery of the same SIGTERM: the
    latch is idempotent — no exception, one recorded preemption."""
    reg = MetricsRegistry()
    guard = PreemptionGuard(registry=reg)
    guard.install()
    try:
        signal.raise_signal(signal.SIGTERM)
        signal.raise_signal(signal.SIGTERM)
        assert guard.requested
        assert reg.counter("resilience/preemptions_total").value == 1
    finally:
        guard.uninstall()


def test_second_sigint_still_escalates_through_chain():
    reg = MetricsRegistry()
    outer = PreemptionGuard(registry=reg)
    inner = PreemptionGuard(registry=reg)
    outer.install()
    try:
        inner.install()
        try:
            signal.raise_signal(signal.SIGINT)  # graceful: latch both
            assert inner.requested and outer.requested
            with pytest.raises(KeyboardInterrupt):
                signal.raise_signal(signal.SIGINT)  # ^C ^C: stop NOW
        finally:
            inner.uninstall()
    finally:
        outer.uninstall()


def test_sigterm_then_one_sigint_stays_graceful_when_chained():
    reg = MetricsRegistry()
    outer = PreemptionGuard(registry=reg)
    inner = PreemptionGuard(registry=reg)
    outer.install()
    try:
        inner.install()
        try:
            signal.raise_signal(signal.SIGTERM)
            signal.raise_signal(signal.SIGINT)  # first ^C after SIGTERM
            assert inner.requested and outer.requested
        finally:
            inner.uninstall()
    finally:
        outer.uninstall()


def test_uninstall_restores_previous_handlers():
    prev = signal.getsignal(signal.SIGTERM)
    guard = PreemptionGuard(registry=MetricsRegistry())
    guard.install()
    assert signal.getsignal(signal.SIGTERM) is not prev
    guard.uninstall()
    assert signal.getsignal(signal.SIGTERM) is prev


# --------------------------------------------------------------------------- #
# role harness + supervisor over real subprocesses
# --------------------------------------------------------------------------- #
def flaky_role(ctx):
    """Crashes on first incarnation, completes after the respawn — the
    supervisor's restart path, end to end."""
    if ctx.spec.incarnation == 0:
        raise RuntimeError("injected first-incarnation crash")

    ticks = {"n": 0}

    def tick():
        ticks["n"] += 1
        return ticks["n"] >= 2

    return tick


def _spec(root, name, target, kwargs=None, **over):
    base = dict(name=name, target=target, root=str(root), member_id=0,
                kwargs=kwargs or {}, lease_timeout=2.0, poll_interval=0.01,
                env=dict(_ENV))
    base.update(over)
    return RoleSpec(**base)


def test_spawn_refuses_a_role_that_is_not_told_its_backend(tmp_path):
    """A chip belongs to one process: a role whose env does not name
    JAX_PLATFORMS would inherit the launcher's, so no process is started."""
    sup = ProcessSupervisor(tmp_path, lease_timeout=2.0,
                            registry=MetricsRegistry())
    spec = _spec(tmp_path, "idle", "agilerl_tpu.training.launch:idle_role",
                 env={"PYTHONPATH": REPO_ROOT})
    with pytest.raises(ValueError, match="JAX_PLATFORMS"):
        sup.spawn(spec)
    assert sup.procs == {} and not (tmp_path / "specs").exists()


def test_role_harness_runs_idle_role_to_done(tmp_path):
    sup = ProcessSupervisor(tmp_path, lease_timeout=2.0,
                            registry=MetricsRegistry())
    sup.spawn(_spec(tmp_path, "idle",
                    "agilerl_tpu.training.launch:idle_role",
                    kwargs={"max_ticks": 3}))
    assert sup.wait(timeout=60.0)
    assert sup.exits == {"idle": EXIT_DONE}
    st = read_statuses(tmp_path)["idle"]
    assert st["state"] == "done" and st["ticks"] == 3
    # graceful completion tombstones the lease
    assert sup.heartbeat.alive() == {}


def test_supervisor_restarts_crashed_role_with_bumped_incarnation(tmp_path):
    reg = MetricsRegistry()
    sup = ProcessSupervisor(tmp_path, lease_timeout=2.0, max_restarts=2,
                            registry=reg)
    sup.spawn(_spec(tmp_path, "flaky",
                    "tests.test_resilience.test_proc:flaky_role"))
    assert sup.wait(timeout=90.0)
    # crashed once (restart), then the incarnation-1 child completed
    assert sup.exits == {"flaky": EXIT_DONE}
    assert sup.restarts == {"flaky": 1}
    assert reg.counter("resilience/proc_restarts_total").value == 1
    st = read_statuses(tmp_path)["flaky"]
    assert st["state"] == "done" and st["incarnation"] == 1


def test_supervisor_gives_up_after_max_restarts(tmp_path):
    def always_crash_spec():
        return _spec(tmp_path, "flaky",
                     "tests.test_resilience.test_proc:always_crash_role")

    sup = ProcessSupervisor(tmp_path, lease_timeout=2.0, max_restarts=1,
                            registry=MetricsRegistry())
    sup.spawn(always_crash_spec())
    assert sup.wait(timeout=90.0)
    assert sup.exits == {"flaky": EXIT_CRASH}
    assert sup.restarts == {"flaky": 1}
    st = read_statuses(tmp_path)["flaky"]
    assert st["state"] == "crashed"
    assert "injected" in st["error"]


def always_crash_role(ctx):
    raise RuntimeError("injected crash (every incarnation)")


def test_launcher_sigterm_drains_fleet_real_subprocesses(tmp_path):
    """The acceptance-criterion drain test: forever-running roles, real
    processes, SIGTERM through the supervisor -> every role exits through
    its PreemptionGuard (drain hook ran, JSONL events flushed, lease
    tombstoned, status committed), and NOTHING is left running."""
    from agilerl_tpu.training.launch import PodLauncher

    launcher = PodLauncher(tmp_path, lease_timeout=2.0, grace_s=15.0)
    for name in ("alpha", "beta"):
        launcher.add_role(name, "agilerl_tpu.training.launch:idle_role",
                          kwargs={"max_ticks": None}, poll_interval=0.02,
                          env=dict(_ENV))
    handlers = [signal.getsignal(s) for s in launcher.guard.signals]
    launcher.start()
    pids = {n: p.pid for n, p in launcher.supervisor.procs.items()}
    summary = launcher.shutdown()
    # the launcher's signal handlers end with its fleet
    assert [signal.getsignal(s) for s in launcher.guard.signals] == handlers

    assert summary["exits"] == {"alpha": EXIT_PREEMPTED,
                                "beta": EXIT_PREEMPTED}
    assert summary["escalated"] == [] and summary["orphans"] == []
    for name in ("alpha", "beta"):
        st = summary["statuses"][name]
        assert st["state"] == "preempted" and st["ticks"] >= 1
        # the role's drain hook ran (final snapshot committed)
        drain = json.loads((tmp_path / f"drain_{name}.json").read_text())
        assert drain["ticks"] == st["ticks"]
        # the JSONL event sink was flushed: the preemption event is durable
        events = read_jsonl(tmp_path / "logs" / f"{name}.events.jsonl")
        assert any(e.get("kind") == "preemption" for e in events)
        assert not pid_alive(pids[name])  # no orphan processes
    # graceful exits tombstoned their leases
    assert launcher.heartbeat.alive() == {}


def test_launcher_kill9_detected_fast_and_restarted(tmp_path):
    """kill -9 a role: the same-host pid probe surfaces the loss on the
    NEXT poll (lease 10000s — only the probe can see it) and the
    supervisor respawns it with a bumped incarnation."""
    from agilerl_tpu.training.launch import PodLauncher

    launcher = PodLauncher(tmp_path, lease_timeout=10_000.0, grace_s=15.0,
                           registry=MetricsRegistry())
    launcher.add_role("victim", "agilerl_tpu.training.launch:idle_role",
                      kwargs={"max_ticks": None}, poll_interval=0.02,
                      env=dict(_ENV))
    launcher.start()
    victim = launcher.supervisor.procs["victim"]
    t0 = time.monotonic()
    os.kill(victim.pid, signal.SIGKILL)
    victim.popen.wait(timeout=10.0)

    # membership sees the crash immediately (pid probe, NOT lease expiry)
    assert launcher.heartbeat.alive() == {}
    detect_s = time.monotonic() - t0
    assert detect_s < 60.0  # vs the 10000s lease window

    events = launcher.poll()
    assert [e["action"] for e in events] == ["restarted"]
    new = launcher.supervisor.procs["victim"]
    assert new.pid != victim.pid and new.spec.incarnation == 1

    # the respawn comes back up as a live member, then drains cleanly
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and not launcher.heartbeat.alive():
        time.sleep(0.05)
    assert launcher.heartbeat.alive()
    summary = launcher.shutdown()
    assert summary["exits"]["victim"] == EXIT_PREEMPTED
    assert summary["orphans"] == []


# --------------------------------------------------------------------------- #
# concurrent multi-process publish_entry racers (satellite)
# --------------------------------------------------------------------------- #
N_RACE_ENTRIES = 24


def race_writer(directory: str, writer: int) -> None:
    """Publish N entries under the SAME names as the sibling writer —
    the pid-prefixed staging must keep the racers out of each other's
    in-flight ``.tmp`` dirs."""
    from agilerl_tpu.resilience.store import publish_entry

    for seq in range(N_RACE_ENTRIES):
        publish_entry(directory, f"entry_{seq:08d}",
                      {"writer": writer, "seq": seq},
                      manifest_extra={"writer": writer, "seq": seq})
    print("WRITER_OK", writer)


def test_publish_entry_concurrent_multiprocess_racers(tmp_path):
    store_dir = tmp_path / "race"
    env = dict(os.environ)
    env.update(_ENV)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c",
             "import sys; from tests.test_resilience.test_proc import "
             f"race_writer; race_writer(sys.argv[1], {w})",
             str(store_dir)],
            env=env, cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        for w in (1, 2)
    ]

    # concurrent reader: every committed entry must load hash-valid or
    # vanish (GC/rewrite) — NEVER a PERSISTENTLY torn read. A transient
    # mismatch while the racing writer swaps the same name is the skip-torn
    # path working as designed; a committed-and-stable entry that stays
    # unreadable would be the real torn-write bug.
    torn = 0
    deadline = time.monotonic() + 120.0
    while any(p.poll() is None for p in procs):
        for entry in committed_entries(store_dir, "entry_"):
            payload = None
            for _ in range(5):  # retries absorb mid-swap transients
                try:
                    payload = read_entry(entry)
                    break
                except (CorruptSnapshotError, OSError):
                    time.sleep(0.005)
            if payload is None:
                if entry.exists():
                    torn += 1
            else:
                assert payload["writer"] in (1, 2)
        assert time.monotonic() < deadline, "racers wedged"
        time.sleep(0.01)

    outs = [p.stdout.read().decode() for p in procs]
    assert [p.wait() for p in procs] == [0, 0], outs
    # neither racer had its in-flight staging rmtree'd by the other
    assert all("WRITER_OK" in o for o in outs), outs
    assert torn == 0

    # final state: every seq committed exactly once, hash-valid, monotone
    entries = committed_entries(store_dir, "entry_")
    assert len(entries) == N_RACE_ENTRIES
    seqs = []
    for entry in entries:
        payload = read_entry(entry)  # raises on torn — must not happen
        assert payload["writer"] in (1, 2)
        seqs.append(payload["seq"])
    assert seqs == sorted(seqs) == list(range(N_RACE_ENTRIES))
    # no staging leftovers
    assert not list(store_dir.glob("*.tmp"))

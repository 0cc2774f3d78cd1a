"""The replica supervisor: exit-code contract, backoff, flap, and the
tentpole attestation — conservation through kill -9.

Unit ring: scripted fake ``Popen`` objects drive the monitor loop
deterministically (seeded jitter rng) — drain-vs-crash exits, backoff
escalation and cap, flap detection, ready-timeout-as-crash, telemetry
event stream. Process ring: real fake-replica children (no jax in the
CHILD) under SIGTERM / SIGKILL / self-crash exit 44. Gateway ring: a
real ``ServingGateway`` over a supervised 2-child fleet takes a seeded
randomized kill -9 schedule mid-traffic — every HTTP request must
still get exactly one terminal, ``check_conservation()`` must hold,
the fleet must heal (restart, rejoin), and a follow-up request must
produce the exact expected tokens.
"""

import itertools
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from scaletorch_tpu.serving.supervisor import ReplicaSupervisor

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
FAKE_REPLICA = os.path.join(TESTS_DIR, "fake_replica.py")

_PIDS = itertools.count(4000)


class FakeStdout:
    def __init__(self, lines):
        self._lines = list(lines)

    def readline(self):
        if self._lines:
            return self._lines.pop(0)
        return ""  # EOF

    def __iter__(self):
        return iter(())


class FakeProc:
    """A scripted Popen double the monitor loop can reap."""

    def __init__(self, *, ready=True, port=7001):
        self.pid = next(_PIDS)
        self.returncode = None
        self.stdout = FakeStdout(
            [f"READY port={port}\n"] if ready else [])
        self.terminated = False
        self.was_killed = False

    def exit(self, code):
        self.returncode = code

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        if self.terminated and self.returncode is None:
            self.returncode = 0
        if self.was_killed and self.returncode is None:
            self.returncode = -9
        if self.returncode is None:
            raise RuntimeError("fake child still running")
        return self.returncode

    def terminate(self):
        self.terminated = True

    def kill(self):
        self.was_killed = True
        if self.returncode is None:
            self.returncode = -9


class RecordingExporter:
    def __init__(self):
        self.records = []

    def emit(self, kind, record):
        self.records.append((kind, dict(record)))


def make_supervisor(spawn_fn, ids=("r0",), **kw):
    kw.setdefault("poll_interval_s", 0.01)
    kw.setdefault("backoff_base_s", 0.02)
    kw.setdefault("backoff_max_s", 0.08)
    kw.setdefault("backoff_jitter", 0.0)
    kw.setdefault("ready_timeout_s", 2.0)
    kw.setdefault("rng", random.Random(0))
    return ReplicaSupervisor(spawn_fn, list(ids), **kw)


def wait_for(predicate, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


class TestExitCodeContract:
    """Unit ring: scripted fake processes, deterministic jitter."""

    def test_exit_zero_is_drained_no_restart(self):
        procs = []

        def spawn(rid):
            procs.append(FakeProc())
            return procs[-1]

        exits = []
        sup = make_supervisor(spawn, on_exit=lambda rid, rc:
                              exits.append((rid, rc)))
        sup.start()
        assert sup.replica_status("r0")["state"] == "up"
        procs[0].exit(0)
        wait_for(lambda: sup.replica_status("r0")["state"] == "drained",
                 msg="drained state")
        time.sleep(0.1)  # give a buggy restart a chance to fire
        assert len(procs) == 1, "exit 0 must NOT respawn"
        assert exits == [("r0", 0)]
        assert sup.replica_status("r0")["restarts_total"] == 0
        sup.stop(drain=False)

    @pytest.mark.parametrize("code", [42, 43, 44, -9, 1])
    def test_crash_family_restarts_with_backoff(self, code):
        procs = []

        def spawn(rid):
            procs.append(FakeProc())
            return procs[-1]

        restarts = []
        sup = make_supervisor(
            spawn, on_restart=lambda rid, w: restarts.append(rid))
        sup.start()
        first_pid = sup.replica_status("r0")["pid"]
        procs[0].exit(code)
        wait_for(lambda: len(procs) == 2, msg="respawn")
        wait_for(lambda: sup.replica_status("r0")["state"] == "up",
                 msg="back up")
        st = sup.replica_status("r0")
        assert st["restarts_total"] == 1
        assert st["last_exit_code"] == code
        assert st["pid"] != first_pid
        # the monitor thread fires on_restart AFTER it has set "up"
        wait_for(lambda: restarts == ["r0"], msg="on_restart")
        sup.stop(drain=False)

    def test_backoff_escalates_and_caps(self):
        sup = make_supervisor(lambda rid: FakeProc(), backoff_base_s=0.5,
                              backoff_max_s=4.0)
        assert sup._backoff_s(1) == 0.5
        assert sup._backoff_s(2) == 1.0
        assert sup._backoff_s(3) == 2.0
        assert sup._backoff_s(4) == 4.0
        assert sup._backoff_s(10) == 4.0  # capped
        jittered = make_supervisor(
            lambda rid: FakeProc(), backoff_base_s=1.0, backoff_max_s=8.0,
            backoff_jitter=0.5, rng=random.Random(7))
        samples = [jittered._backoff_s(1) for _ in range(50)]
        assert all(1.0 <= s <= 1.5 for s in samples)
        assert len(set(samples)) > 1, "jitter must actually vary"

    def test_flapping_marks_failed_permanently(self):
        procs = []

        def spawn(rid):
            procs.append(FakeProc())
            return procs[-1]

        sup = make_supervisor(spawn, flap_window_s=60.0,
                              flap_max_restarts=3)
        sup.start()

        def crash_latest():
            procs[-1].exit(42)

        for _ in range(2):
            n = len(procs)
            crash_latest()
            wait_for(lambda: len(procs) == n + 1, msg="respawn")
            wait_for(lambda: sup.replica_status("r0")["state"] == "up",
                     msg="back up")
        crash_latest()  # 3rd crash inside the window -> flapping
        wait_for(lambda: sup.replica_status("r0")["state"] == "failed",
                 msg="failed state")
        spawned = len(procs)
        time.sleep(0.15)
        assert len(procs) == spawned, "failed replica must not respawn"
        assert sup.replica_status("r0")["restarts_total"] == 2
        sup.stop(drain=False)

    def test_healthy_uptime_resets_consecutive(self):
        procs = []

        def spawn(rid):
            procs.append(FakeProc())
            return procs[-1]

        # healthy_reset_s=0: every uptime counts as healthy, so the
        # backoff exponent never escalates while total keeps counting
        sup = make_supervisor(spawn, healthy_reset_s=0.0,
                              flap_window_s=0.01, flap_max_restarts=100)
        sup.start()
        for n in (1, 2):
            procs[-1].exit(42)
            wait_for(lambda: len(procs) == n + 1, msg="respawn")
            wait_for(lambda: sup.replica_status("r0")["state"] == "up",
                     msg="back up")
            st = sup.replica_status("r0")
            assert st["restarts_consecutive"] == 1
            assert st["restarts_total"] == n
        sup.stop(drain=False)

    def test_first_boot_failure_raises(self):
        with pytest.raises(RuntimeError, match="first boot"):
            make_supervisor(
                lambda rid: FakeProc(ready=False), ready_timeout_s=0.5
            ).start()

    def test_telemetry_event_stream(self):
        procs = []

        def spawn(rid):
            procs.append(FakeProc())
            return procs[-1]

        exp = RecordingExporter()
        sup = make_supervisor(spawn, exporter=exp)
        sup.start()
        procs[0].exit(44)
        wait_for(lambda: len(procs) == 2, msg="respawn")
        wait_for(lambda: sup.replica_status("r0")["state"] == "up",
                 msg="back up")
        sup.stop(drain=False)
        assert all(kind == "supervisor" for kind, _ in exp.records)
        events = [r["event"] for _, r in exp.records]
        assert events[:2] == ["spawn", "ready"]
        assert "crash" in events and "restart" in events
        crash = next(r for _, r in exp.records if r["event"] == "crash")
        assert crash["exit_code"] == 44
        assert crash["replica"] == "r0"
        assert crash["backoff_s"] >= 0


class TestRealChildren:
    """Process ring: real (jax-free) fake-replica children."""

    def _spawner(self, *extra):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            TESTS_DIR)) + os.pathsep + env.get("PYTHONPATH", "")

        def spawn(rid):
            return subprocess.Popen(
                [sys.executable, FAKE_REPLICA, "--replica_id", rid,
                 *extra],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env)

        return spawn

    def test_drain_vs_crash_exit_codes(self):
        sup = make_supervisor(self._spawner(), ids=("a", "b"),
                              ready_timeout_s=30.0)
        sup.start()
        try:
            status = sup.status()
            assert {status["a"]["state"], status["b"]["state"]} == {"up"}
            with sup._lock:
                proc_a = sup._replicas["a"].proc
                proc_b = sup._replicas["b"].proc
            # SIGTERM -> clean drain, exit 0, no restart
            proc_a.terminate()
            wait_for(lambda: sup.replica_status("a")["state"] == "drained",
                     timeout=20, msg="a drained")
            assert sup.replica_status("a")["last_exit_code"] == 0
            assert sup.replica_status("a")["restarts_total"] == 0
            # SIGKILL -> crash family, restarted with a NEW pid
            old_pid = sup.replica_status("b")["pid"]
            proc_b.kill()
            wait_for(lambda: sup.replica_status("b")["restarts_total"] == 1,
                     timeout=20, msg="b restarted")
            wait_for(lambda: sup.replica_status("b")["state"] == "up",
                     timeout=20, msg="b back up")
            st = sup.replica_status("b")
            assert st["last_exit_code"] == -signal.SIGKILL
            assert st["pid"] not in (None, old_pid)
        finally:
            sup.stop(drain=False)

    def test_selfcrash_exit_code_recorded_and_restarted(self):
        sup = make_supervisor(
            self._spawner("--selfcrash_after_s", "0.3",
                          "--selfcrash_code", "44"),
            ready_timeout_s=30.0, flap_max_restarts=50,
            flap_window_s=0.001)
        sup.start()
        try:
            wait_for(lambda:
                     sup.replica_status("r0")["restarts_total"] >= 1,
                     timeout=20, msg="restart after exit 44")
            assert sup.replica_status("r0")["last_exit_code"] == 44
        finally:
            sup.stop(drain=False)


class TestGatewayConservationUnderCrashes:
    """Gateway ring: randomized kill -9 schedule vs a supervised fleet.

    The tentpole invariant: ``http_requests_received == sum(outcomes)``
    survives replica processes dying mid-stream, and the fleet heals.
    """

    def _build(self, tmp_path):
        from scaletorch_tpu.serving.gateway import ServingGateway
        from scaletorch_tpu.serving.remote import RemoteEngineWorker

        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            TESTS_DIR)) + os.pathsep + env.get("PYTHONPATH", "")

        def spawn(rid):
            return subprocess.Popen(
                [sys.executable, FAKE_REPLICA, "--replica_id", rid,
                 "--token_delay_s", "0.01"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env)

        sup = ReplicaSupervisor(
            spawn, ["r0", "r1"],
            worker_factory=lambda rid, port, proc: RemoteEngineWorker(
                "127.0.0.1", port, replica_id=rid, proc=proc,
                poll_interval_s=0.03).start(),
            poll_interval_s=0.01, backoff_base_s=0.05, backoff_max_s=0.2,
            backoff_jitter=0.0, flap_window_s=0.5, flap_max_restarts=20,
            ready_timeout_s=30.0, rng=random.Random(0))
        workers = sup.start()
        gw = ServingGateway(workers, port=0, supervisor=sup,
                            max_backlog=512).start_in_thread()
        return gw, sup

    def test_conservation_through_randomized_kill9(self, tmp_path):
        from .fake_replica import FakeEngineWorker

        gw, sup = self._build(tmp_path)
        rng = random.Random(1234)
        stop_killing = threading.Event()
        kills = []

        def killer():
            while not stop_killing.is_set():
                time.sleep(rng.uniform(0.15, 0.4))
                if stop_killing.is_set():
                    break  # no straggler kill after the clients finish
                with sup._lock:
                    up = [r for r in sup._replicas.values()
                          if r.state == "up" and r.proc is not None
                          and r.proc.poll() is None]
                if not up:
                    continue
                victim = rng.choice(up)
                victim.proc.kill()
                kills.append(victim.replica_id)

        outcomes = []

        def client(seed):
            crng = random.Random(seed)
            for _ in range(6):
                prompt = [crng.randrange(1, 50)
                          for _ in range(crng.randrange(1, 5))]
                body = json.dumps({
                    "prompt": prompt,
                    "max_new_tokens": crng.randrange(4, 30),
                    "stream": False}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{gw.port}/v1/generate",
                    data=body, method="POST")
                try:
                    resp = urllib.request.urlopen(req, timeout=30)
                    payload = json.loads(resp.read())
                except urllib.error.HTTPError as err:
                    payload = json.loads(err.read())
                outcomes.append(payload["outcome"])

        kt = threading.Thread(target=killer, daemon=True)
        kt.start()
        clients = [threading.Thread(target=client, args=(s,), daemon=True)
                   for s in range(4)]
        try:
            for t in clients:
                t.start()
            for t in clients:
                t.join(timeout=120)
                assert not t.is_alive(), "client wedged without terminal"
            stop_killing.set()
            kt.join(timeout=5)

            # every request got exactly one terminal outcome
            assert len(outcomes) == 24
            assert kills, "the schedule never actually killed a child"
            # the ledger balances THROUGH the crashes
            gw.metrics.check_conservation()
            # the fleet healed: kills were restarted. Require a LIVE
            # process, not just state "up" — a corpse the monitor has
            # not reaped yet still reads "up" for a poll interval.
            def healed():
                with sup._lock:
                    return all(r.state == "up" and r.proc is not None
                               and r.proc.poll() is None
                               for r in sup._replicas.values())

            wait_for(healed, timeout=30, msg="fleet healed")
            total_restarts = sum(st["restarts_total"]
                                 for st in sup.status().values())
            assert total_restarts >= 1
            # and a restarted fleet still serves CORRECT tokens
            oracle = FakeEngineWorker()
            body = json.dumps({"prompt": [11, 7], "max_new_tokens": 5,
                               "stream": False}).encode()
            resp = urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{gw.port}/v1/generate", data=body,
                method="POST"), timeout=30)
            payload = json.loads(resp.read())
            assert payload["outcome"] == "ok"
            assert payload["token_ids"] == \
                oracle.expected_tokens([11, 7], 5)
            # process state is on /healthz
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{gw.port}/healthz",
                timeout=10).read())
            for rid in ("r0", "r1"):
                rep = health["replicas"][rid]
                assert rep["state"] == "up"
                assert isinstance(rep["pid"], int)
                assert rep["restarts_total"] >= 0
            # ...and on /metrics as a labelled counter
            metrics = urllib.request.urlopen(
                f"http://127.0.0.1:{gw.port}/metrics",
                timeout=10).read().decode()
            assert "replica_restarts_total" in metrics
            assert 'replica_up{replica="r0"}' in metrics
        finally:
            stop_killing.set()
            gw.stop_sync()
            sup.stop(drain=False)

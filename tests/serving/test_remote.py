"""The replica wire: RemoteEngineWorker <-> ReplicaServer.

Three rings, inside out: (1) the wire alone — an in-process
``ReplicaServer`` over the jax-free ``FakeEngineWorker`` double, the
``RemoteEngineWorker`` client talking real HTTP/SSE to it; (2) real
child processes (fake_replica.py) — kill -9 mid-stream must synthesize
exactly one ``aborted`` terminal and flip ``alive``; SIGTERM must drain
to exit 0; (3) the acceptance attestation — a real tiny-Llama engine
behind the wire produces BIT-IDENTICAL greedy tokens to the same engine
driven directly, with ``decode_compile_count == 1`` (the process
boundary adds zero retraces).
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from scaletorch_tpu.serving.protocol import parse_generate_request
from scaletorch_tpu.serving.remote import RemoteEngineWorker, ReplicaServer

from .fake_replica import FakeEngineWorker

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
FAKE_REPLICA = os.path.join(TESTS_DIR, "fake_replica.py")


def make_req(prompt, n, **kw):
    body = {"prompt": list(prompt), "max_new_tokens": n, "stream": True}
    body.update(kw)
    return parse_generate_request(json.dumps(body).encode())


class ServerThread:
    """An in-process ReplicaServer on its own event-loop thread."""

    def __init__(self, worker):
        self.worker = worker
        self.server = None
        self.port = None
        self._loop = None
        self._started = threading.Event()
        self._thread = threading.Thread(
            target=lambda: asyncio.run(self._main()),
            name="replica-server-test", daemon=True)

    async def _main(self):
        self._loop = asyncio.get_running_loop()
        self.server = ReplicaServer(self.worker, port=0)
        await self.server.start()
        self.port = self.server.port
        self._started.set()
        await self.server.wait_drain()
        deadline = time.monotonic() + 5.0
        while self.worker.inflight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        await self.server.close()

    def start(self):
        self._thread.start()
        assert self._started.wait(10), "replica server never bound"
        return self

    def stop(self):
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self.server.request_drain)
        self._thread.join(10)


def run_request(remote, req, *, timeout=30):
    """Submit through the remote handle; block for the terminal."""
    done = threading.Event()
    out = {"tokens": [], "result": None, "submitted": None}

    remote.submit(
        req,
        lambda rid, toks, _t: out["tokens"].extend(toks),
        lambda res: (out.__setitem__("result", res), done.set()),
        ttl_s=req.ttl_s,
        on_submitted=lambda rid: out.__setitem__("submitted", rid),
    )
    assert done.wait(timeout), "no terminal result"
    return out


def spawn_fake_child(*extra_args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(TESTS_DIR)) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, FAKE_REPLICA, *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"fake replica died before READY rc={proc.poll()}")
        if line.startswith("READY port="):
            return proc, int(line.strip().split("=")[1])
    raise RuntimeError("fake replica never printed READY")


class TestWireInProcess:
    """Ring 1: the wire alone, no child processes, no jax engine."""

    def test_stream_roundtrip_and_payload(self):
        worker = FakeEngineWorker(token_delay_s=0.0)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            assert remote.alive
            assert remote.page_size == worker.page_size
            out = run_request(remote, make_req([3, 1, 4], 6))
            res = out["result"]
            assert res.outcome == "ok"
            assert res.finish_reason == "length"
            expect = worker.expected_tokens([3, 1, 4], 6)
            assert out["tokens"] == expect
            assert res.tokens == expect
            assert out["submitted"] == res.request_id
            # the terminal carries the engine's latency attribution
            assert res.queue_wait_s == 0.0
            assert res.prefill_s == 0.0
            assert res.prefix_hit is False
            assert (res.stall_s, res.device_wait_s, res.host_s) == (
                0.25, 0.5, 0.125)
            assert remote.inflight == 0
        finally:
            remote.stop_polling()
            srv.stop()

    def test_stop_polling_joins_the_poller(self):
        """stop_polling must wait for the poller thread, not just flag
        it — a replaced worker's poller may not outlive its successor
        (the ST1101 finding that seeded the ownership tier)."""
        worker = FakeEngineWorker(token_delay_s=0.0)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            assert remote._poller.is_alive()
        finally:
            remote.stop_polling()
            srv.stop()
        assert not remote._poller.is_alive()
        # before start() the poller has no ident: stop must not raise
        fresh = RemoteEngineWorker("127.0.0.1", srv.port, replica_id="rx")
        fresh.stop_polling()
        assert not fresh._poller.is_alive()

    def test_trace_id_rides_the_hop(self):
        worker = FakeEngineWorker(token_delay_s=0.0)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            req = make_req([5, 5], 2)
            req.trace_id = "a" * 32
            res = run_request(remote, req)["result"]
            assert res.trace_id == "a" * 32
        finally:
            remote.stop_polling()
            srv.stop()

    def test_cancel_mid_stream_aborts(self):
        worker = FakeEngineWorker(token_delay_s=0.05)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            done = threading.Event()
            got = {}
            submitted = threading.Event()
            rid_box = {}

            def on_submitted(rid):
                rid_box["rid"] = rid
                submitted.set()

            remote.submit(
                make_req([9, 9], 200),
                lambda rid, toks, _t: None,
                lambda res: (got.__setitem__("res", res), done.set()),
                on_submitted=on_submitted)
            assert submitted.wait(10)
            remote.cancel(rid_box["rid"], "test cancel")
            assert done.wait(10)
            assert got["res"].outcome == "aborted"
            assert got["res"].detail == "test cancel"
            assert remote.inflight == 0
        finally:
            remote.stop_polling()
            srv.stop()

    def test_gauges_polled_and_ticks_fire(self):
        worker = FakeEngineWorker(token_delay_s=0.0)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0",
            poll_interval_s=0.02).start()
        try:
            ticks = []
            remote.tick_listeners.append(lambda: ticks.append(1))
            deadline = time.monotonic() + 5
            while not remote.gauges() and time.monotonic() < deadline:
                time.sleep(0.02)
            gauges = remote.gauges()
            assert gauges["page_pool_free"] == float(worker.page_pool)
            assert "slot_occupancy" in gauges
            assert ticks, "poller never fired tick listeners"
            assert remote.pid == os.getpid()  # in-process server
        finally:
            remote.stop_polling()
            srv.stop()

    def test_refused_submit_is_rejected_terminal(self):
        """A 4xx on /v1/submit still yields exactly one terminal."""
        worker = FakeEngineWorker(token_delay_s=0.0)
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            req = make_req([1], 1)
            req.prompt = []  # invalid on the wire: parse rejects it
            res = run_request(remote, req)["result"]
            assert res.outcome == "rejected"
            assert "refused" in res.detail
        finally:
            remote.stop_polling()
            srv.stop()


class TestChildProcess:
    """Ring 2: real fake-replica children; crash and drain semantics."""

    def test_kill9_mid_stream_synthesizes_one_aborted(self):
        proc, port = spawn_fake_child("--token_delay_s", "0.05")
        remote = RemoteEngineWorker(
            "127.0.0.1", port, replica_id="r0", proc=proc,
            poll_interval_s=0.02).start()
        try:
            done = threading.Event()
            got = {"tokens": [], "dones": []}
            remote.submit(
                make_req([2, 7], 500),
                lambda rid, toks, _t: got["tokens"].extend(toks),
                lambda res: (got["dones"].append(res), done.set()))
            deadline = time.monotonic() + 10
            while len(got["tokens"]) < 2 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert got["tokens"], "no tokens before the kill"
            remote.kill()
            assert done.wait(10)
            time.sleep(0.3)  # any late duplicate terminal would land now
            assert len(got["dones"]) == 1, "exactly one terminal"
            res = got["dones"][0]
            assert res.outcome == "aborted"
            # partial progress is preserved on the synthesized terminal
            assert res.tokens == got["tokens"]
            deadline = time.monotonic() + 5
            while remote.alive and time.monotonic() < deadline:
                time.sleep(0.02)
            assert not remote.alive
            assert remote.exit_code == -signal.SIGKILL
            assert remote.inflight == 0
        finally:
            remote.stop_polling()
            if proc.poll() is None:
                proc.kill()
            proc.wait(10)

    def test_drain_exits_zero(self):
        proc, port = spawn_fake_child()
        remote = RemoteEngineWorker(
            "127.0.0.1", port, replica_id="r0", proc=proc).start()
        try:
            res = run_request(remote, make_req([1, 2], 3))["result"]
            assert res.outcome == "ok"
            remote.shutdown(drain=True)
            remote.join(timeout=15)
            assert proc.poll() == 0, "clean drain must exit 0"
            assert remote.exit_code == 0
        finally:
            remote.stop_polling()
            if proc.poll() is None:
                proc.kill()
            proc.wait(10)

    def test_sigterm_drains_inflight_first(self):
        """SIGTERM mid-stream: the in-flight request still gets its
        real terminal (ok, full tokens), THEN the child exits 0."""
        proc, port = spawn_fake_child("--token_delay_s", "0.02")
        remote = RemoteEngineWorker(
            "127.0.0.1", port, replica_id="r0", proc=proc).start()
        try:
            done = threading.Event()
            got = {}
            remote.submit(
                make_req([4, 4], 20),
                lambda rid, toks, _t: None,
                lambda res: (got.__setitem__("res", res), done.set()))
            time.sleep(0.1)  # a few tokens in
            proc.send_signal(signal.SIGTERM)
            assert done.wait(15)
            assert got["res"].outcome == "ok"
            assert len(got["res"].tokens) == 20
            proc.wait(15)
            assert proc.returncode == 0
        finally:
            remote.stop_polling()
            if proc.poll() is None:
                proc.kill()
            proc.wait(10)


class TestEngineParity:
    """Ring 3: a REAL engine behind the wire — bit-identical greedy
    tokens vs the same engine driven directly, one decode compile."""

    @pytest.fixture(scope="class")
    def tiny(self):
        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.models import llama

        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, dtype=jnp.float32)
        return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)

    def _make_engine(self, tiny):
        from scaletorch_tpu.inference import InferenceEngine, SamplingParams

        cfg, params = tiny
        return InferenceEngine(
            params, cfg, max_slots=2, max_seq=32, prefill_len=16,
            sampling=SamplingParams(temperature=0.0),
            page_size=4, strict_submit=False)

    def test_remote_bit_identical_one_compile(self, tiny):
        from scaletorch_tpu.serving.gateway import EngineWorker

        prompts = [[1, 2, 3], [7, 8, 9, 10], [4, 4, 4]]
        # oracle: the same engine driven directly
        oracle = self._make_engine(tiny)
        expect = {}
        for prompt in prompts:
            rid = oracle.submit(list(prompt), max_new_tokens=6)
            expect[tuple(prompt)] = oracle.run()[rid].tokens

        engine = self._make_engine(tiny)
        worker = EngineWorker(engine, replica_id="r0").start()
        srv = ServerThread(worker).start()
        remote = RemoteEngineWorker(
            "127.0.0.1", srv.port, replica_id="r0").start()
        try:
            for prompt in prompts:
                out = run_request(remote, make_req(prompt, 6), timeout=120)
                res = out["result"]
                assert res.outcome == "ok", res.detail
                assert res.tokens == expect[tuple(prompt)], prompt
                assert out["tokens"] == expect[tuple(prompt)], prompt
            assert engine.decode_compile_count == 1
            assert 1 <= engine.prefill_compile_count <= len(engine.prefill_shapes)
            # the wire surfaces the compile count for CI to assert on
            metrics = remote._get_json("/metrics")
            assert metrics["decode_compile_count"] == 1
        finally:
            remote.stop_polling()
            srv.stop()
            worker.shutdown(drain=False)
            worker.join(timeout=10)

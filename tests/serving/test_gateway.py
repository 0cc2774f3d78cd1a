"""Gateway end-to-end over real HTTP: SSE bit-parity, conservation,
fairness, drills, drain.

Quick tier, CPU. Each test boots a real ``ServingGateway`` (ephemeral
port, background event-loop thread) over real tiny-Llama engines and
talks to it with urllib / raw sockets — the full stack a production
client would traverse, minus only the network.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
)
from scaletorch_tpu.models import llama
from scaletorch_tpu.serving.admission import TenantConfig
from scaletorch_tpu.serving.gateway import ServingGateway
from scaletorch_tpu.serving.protocol import (
    STATUS_BY_OUTCOME,
    parse_sse_stream,
    stream_tokens,
)
from scaletorch_tpu.telemetry.export import TelemetryExporter, read_jsonl

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
PAGE = 4


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    kw.setdefault("page_size", PAGE)
    kw.setdefault("strict_submit", False)
    return InferenceEngine(params, cfg, **kw)


def post(port, body, *, timeout=60, headers=()):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(), method="POST")
    for k, v in headers:
        req.add_header(k, v)
    try:
        resp = urllib.request.urlopen(req, timeout=timeout)
        return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), err.read()


def get(port, path, timeout=30):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout)
        return resp.status, resp.read()
    except urllib.error.HTTPError as err:
        return err.code, err.read()


def ref_tokens(tiny_llama, prompt, n):
    """Direct-engine greedy oracle (no gateway)."""
    eng = make_engine(tiny_llama)
    rid = eng.submit(prompt, max_new_tokens=n)
    return eng.run()[rid].tokens


class TestStreamingParity:
    def test_sse_stream_bit_identical_and_one_compile(self, tiny_llama):
        """Acceptance: SSE-streamed tokens == direct engine bit-for-bit
        and the bridge adds zero retraces."""
        engine = make_engine(tiny_llama)
        gw = ServingGateway(engine, port=0).start_in_thread()
        try:
            prompts = [[1, 2, 3], [7, 8, 9, 10], [4, 4, 4]]
            for prompt in prompts:
                status, _, raw = post(
                    gw.port,
                    {"prompt": prompt, "max_new_tokens": 6, "stream": True})
                assert status == 200
                events = parse_sse_stream(raw)
                dones = [d for e, d in events if e == "done"]
                assert len(dones) == 1, events
                assert dones[0]["outcome"] == "ok"
                streamed = stream_tokens(events)
                assert streamed == dones[0]["token_ids"]
                assert streamed == ref_tokens(tiny_llama, prompt, 6)
            assert engine.decode_compile_count == 1
            assert 1 <= engine.prefill_compile_count <= len(engine.prefill_shapes)
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()

    def test_unary_response_and_usage(self, tiny_llama):
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            status, _, raw = post(
                gw.port, {"prompt": [5, 6], "max_new_tokens": 4,
                          "stream": False})
            assert status == 200
            payload = json.loads(raw)
            assert payload["outcome"] == "ok"
            assert payload["finish_reason"] == "length"
            assert payload["token_ids"] == ref_tokens(tiny_llama, [5, 6], 4)
            assert payload["usage"] == {"prompt_tokens": 2,
                                       "completion_tokens": 4}
        finally:
            gw.stop_sync()

    def test_malformed_request_is_400_rejected(self, tiny_llama):
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            status, _, raw = post(gw.port, {"prompt": []})
            assert status == 400
            assert json.loads(raw)["outcome"] == "rejected"
            status, _, _ = post(
                gw.port, {"prompt": [1] * 500, "stream": False})
            assert status == 503  # over prefill_len: engine rejects
            assert gw.metrics.outcomes["rejected"] == 2
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()


class TestObservability:
    def test_healthz_metrics_and_jsonl_parity(self, tiny_llama, tmp_path):
        exporter = TelemetryExporter(str(tmp_path / "gw.jsonl"))
        gw = ServingGateway(
            make_engine(tiny_llama), port=0, exporter=exporter,
            export_every=1).start_in_thread()
        try:
            status, raw = get(gw.port, "/healthz")
            assert status == 200
            health = json.loads(raw)
            assert health["status"] == "ok"
            assert health["replicas"]["r0"]["alive"] is True
            assert "page_pool_free" in health["replicas"]["r0"]

            post(gw.port, {"prompt": [1, 2], "max_new_tokens": 2,
                           "stream": False})
            status, raw = get(gw.port, "/metrics")
            assert status == 200
            text = raw.decode()
            for needle in (
                "scaletorch_http_requests_received",
                "scaletorch_sse_streams_open",
                "scaletorch_gateway_shed_total",
                "scaletorch_router_prefix_route_rate",
                # replica identity rides a LABEL, not the metric name
                'scaletorch_engine_pages_in_use{replica="r0"}',
                'scaletorch_engine_queue_depth{replica="r0"}',
                # tenant-labeled latency histograms: real histogram
                # TYPE with _bucket/_sum/_count and an le label
                "# TYPE scaletorch_request_ttft_seconds histogram",
                'scaletorch_request_ttft_seconds_bucket{le=',
                'scaletorch_request_ttft_seconds_count{tenant="default"} 1',
                'scaletorch_request_e2e_seconds_sum{tenant="default"}',
                'scaletorch_request_queue_wait_seconds_count'
                '{tenant="default"} 1',
            ):
                assert needle in text, f"missing {needle}"
        finally:
            gw.stop_sync()
        exporter.close()
        events = read_jsonl(str(tmp_path / "gw.jsonl"))
        assert events, "no gateway_metrics records exported"
        by_kind = {}
        for event in events:
            assert event["v"] == 1
            by_kind.setdefault(event["kind"], []).append(event)
        for event in by_kind["gateway_metrics"]:
            assert "http_requests_received" in event
        assert by_kind["gateway_metrics"][-1]["http_ok"] == 1
        # one access record per terminal HTTP outcome
        access = by_kind["access"]
        assert len(access) == 1
        rec = access[0]
        assert rec["tenant"] == "default"
        assert rec["outcome"] == "ok" and rec["status"] == 200
        assert rec["replica"] == "r0"
        assert rec["tokens"] == 2 and rec["prompt_tokens"] == 2
        assert isinstance(rec["trace_id"], str) and len(rec["trace_id"]) == 32
        assert rec["ttft_s"] > 0 and rec["e2e_s"] >= rec["ttft_s"]
        assert rec["queue_wait_s"] >= 0
        assert rec["prefix_hit"] is False
        # where the stream's time went after its first token: the three
        # engine clocks, in all and per decode step (one step here)
        clocks = [rec[k] for k in ("stall_s", "device_wait_s", "host_s")]
        assert all(c >= 0 for c in clocks) and rec["device_wait_s"] > 0
        assert sum(clocks) <= rec["e2e_s"]
        for key in ("stall_s", "device_wait_s", "host_s"):
            assert rec[f"{key}_per_token"] == rec[key]
        # the mergeable per-tenant histogram state rode the same stream
        assert "latency_histograms" in by_kind

    @pytest.mark.parametrize("tokens, per_token", [
        (5, {"stall_s_per_token": 0.05, "device_wait_s_per_token": 0.1,
             "host_s_per_token": 0.025}),
        (1, None),    # one token: no decode step to divide by
        (0, None),
    ])
    def test_access_record_clock_fields(self, tokens, per_token):
        from scaletorch_tpu.inference.engine import RequestResult
        from scaletorch_tpu.serving.gateway import _decode_clock_fields

        totals = {"stall_s": 0.2, "device_wait_s": 0.4, "host_s": 0.1}
        fields = _decode_clock_fields(RequestResult(
            request_id=1, prompt=[1], tokens=list(range(tokens)),
            finish_reason="length", **totals))
        assert {k: fields[k] for k in totals} == totals
        for key in totals:
            want = per_token[f"{key}_per_token"] if per_token else None
            assert fields[f"{key}_per_token"] == want
        # never dispatched, or retired before its first token: all null
        unserved = RequestResult(request_id=2, prompt=[1], tokens=[],
                                 finish_reason="shed", outcome="shed")
        for result in (None, unserved):
            nulls = _decode_clock_fields(result)
            assert len(nulls) == 6 and set(nulls.values()) == {None}

    def test_404_and_405(self, tiny_llama):
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            assert get(gw.port, "/nope")[0] == 404
            # malformed framing is a CLIENT error, never a logged 500
            sock = socket.create_connection(("127.0.0.1", gw.port),
                                            timeout=30)
            sock.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                         b"Content-Length: abc\r\n\r\n")
            reply = b""
            while True:
                chunk = sock.recv(4096)
                if not chunk:
                    break
                reply += chunk
            sock.close()
            assert reply.startswith(b"HTTP/1.1 400"), reply[:60]
            req = urllib.request.Request(
                f"http://127.0.0.1:{gw.port}/v1/generate", method="GET")
            try:
                status = urllib.request.urlopen(req, timeout=30).status
            except urllib.error.HTTPError as err:
                status = err.code
            assert status == 405
        finally:
            gw.stop_sync()


class TestKeepAlive:
    """ROADMAP front-door item: scrape-heavy Prometheus consumers must
    not pay a TCP connection per scrape — GET /metrics and /healthz
    hold the connection open (HTTP/1.1 keep-alive) until the client
    says Connection: close."""

    @staticmethod
    def _get_on(sock, path, close=False):
        extra = "Connection: close\r\n" if close else ""
        sock.sendall(
            f"GET {path} HTTP/1.1\r\nHost: x\r\n{extra}\r\n".encode())
        buf = b""
        while b"\r\n\r\n" not in buf:
            chunk = sock.recv(4096)
            assert chunk, f"connection closed mid-response: {buf!r}"
            buf += chunk
        head, _, body = buf.partition(b"\r\n\r\n")
        headers = head.decode().split("\r\n")
        length = next(int(h.split(":", 1)[1]) for h in headers
                      if h.lower().startswith("content-length"))
        while len(body) < length:
            chunk = sock.recv(4096)
            assert chunk, "connection closed mid-body"
            body += chunk
        return headers, body[:length]

    def test_scrape_connection_reuse(self, tiny_llama):
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            sock = socket.create_connection(("127.0.0.1", gw.port),
                                            timeout=30)
            try:
                # three requests over ONE connection, mixed endpoints
                for path in ("/metrics", "/healthz", "/metrics"):
                    headers, body = self._get_on(sock, path)
                    assert headers[0].startswith("HTTP/1.1 200"), headers
                    assert any("connection: keep-alive" in h.lower()
                               for h in headers), headers
                    assert body
                # Connection: close is honored: response says close and
                # the server actually closes
                headers, _ = self._get_on(sock, "/healthz", close=True)
                assert any("connection: close" in h.lower()
                           for h in headers), headers
                sock.settimeout(10)
                assert sock.recv(4096) == b""
            finally:
                sock.close()
        finally:
            gw.stop_sync()


class TestRequestTracing:
    TRACE = "0af7651916cd43dd8448eb211c80319c"

    def test_spans_correlated_across_threads_and_echoed(self, tiny_llama):
        """One request's spans appear on BOTH the gateway (asyncio)
        thread and the engine worker thread, correlated by the client's
        trace id; the response echoes a traceparent and the terminal
        payload carries the trace id."""
        from scaletorch_tpu.telemetry.spans import SpanTracer

        tracer = SpanTracer(path=None, role="serve")  # memory-only tail
        engine = make_engine(tiny_llama, tracer=tracer)
        gw = ServingGateway(engine, port=0,
                            tracer=tracer).start_in_thread()
        try:
            status, headers, raw = post(
                gw.port,
                {"prompt": [1, 2, 3], "max_new_tokens": 4, "stream": True},
                headers=[("traceparent",
                          f"00-{self.TRACE}-b7ad6b7169203331-01")])
            assert status == 200
            assert headers.get("traceparent", "").startswith(
                f"00-{self.TRACE}-")
            dones = [d for e, d in parse_sse_stream(raw) if e == "done"]
            assert dones[0]["trace_id"] == self.TRACE

            # a MALFORMED traceparent degrades to a fresh trace — the
            # request still succeeds and gets a well-formed id
            status, headers2, raw2 = post(
                gw.port,
                {"prompt": [4, 5], "max_new_tokens": 2, "stream": False},
                headers=[("traceparent", "garbage-in")])
            assert status == 200
            fresh = json.loads(raw2)["trace_id"]
            assert len(fresh) == 32 and fresh != self.TRACE
            assert headers2.get("traceparent", "").startswith(f"00-{fresh}")
        finally:
            gw.stop_sync()
        ours = [e for e in tracer.tail() if e.get("id") == self.TRACE]
        names = {e["name"] for e in ours}
        assert {"gw.request", "gw.queued", "gw.stream"} <= names, names
        assert {"request", "req.queued", "req.prefill", "req.decode",
                "req.finalize"} <= names, names
        gw_tids = {e["tid"] for e in ours if e["name"].startswith("gw.")}
        eng_tids = {e["tid"] for e in ours if e["name"].startswith("req.")}
        assert gw_tids and eng_tids and not (gw_tids & eng_tids), (
            gw_tids, eng_tids)
        finalize = [e for e in ours if e["name"] == "req.finalize"]
        assert finalize[0]["args"]["outcome"] == "ok"

    def test_untraced_gateway_works_without_tracer(self, tiny_llama):
        """No tracer attached: the request still gets a trace id (for
        the access log) and everything else behaves identically."""
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            status, _, raw = post(
                gw.port, {"prompt": [1], "max_new_tokens": 2,
                          "stream": False})
            assert status == 200
            assert len(json.loads(raw)["trace_id"]) == 32
        finally:
            gw.stop_sync()


class TestSLOHealthz:
    def test_healthz_carries_live_slo_verdict(self, tiny_llama):
        targets = {"min_requests": 1, "error_budget": 0.5,
                   "targets": {"ttft_p95_s": 300.0, "e2e_p99_s": 300.0}}
        gw = ServingGateway(make_engine(tiny_llama), port=0,
                            slo_targets=targets).start_in_thread()
        try:
            status, raw = get(gw.port, "/healthz")
            slo = json.loads(raw)["slo"]
            assert slo["ok"] is True and slo.get("insufficient_data")
            post(gw.port, {"prompt": [1, 2], "max_new_tokens": 2,
                           "stream": False})
            status, raw = get(gw.port, "/healthz")
            assert status == 200
            slo = json.loads(raw)["slo"]
            assert slo["ok"] is True and slo["requests"] == 1
            assert {c["name"] for c in slo["checks"]} == {
                "error_budget", "ttft_p95_s", "e2e_p99_s"}
        finally:
            gw.stop_sync()

    def test_refusals_do_not_feed_latency_histograms(self, tiny_llama):
        """A 400/shed terminal takes microseconds — it must count as an
        outcome but never as a latency observation, or overload would
        drag the SLO quantiles DOWN (confirmed-bug regression)."""
        gw = ServingGateway(make_engine(tiny_llama),
                            port=0).start_in_thread()
        try:
            post(gw.port, {"prompt": []})  # 400 rejected
            post(gw.port, {"prompt": [1, 2], "max_new_tokens": 2,
                           "stream": False})
        finally:
            gw.stop_sync()
        assert gw.metrics.outcomes["rejected"] == 1
        assert gw.metrics.outcomes["ok"] == 1
        assert gw.hists.merged("e2e").count == 1  # the served request only

    def test_healthz_slo_violation_reported_not_fatal(self, tiny_llama):
        """An SLO violation is a VERDICT on /healthz, not an outage:
        the endpoint stays 200 (liveness and latency budgets are
        different alarms)."""
        targets = {"min_requests": 1, "error_budget": 1.0,
                   "targets": {"ttft_p95_s": 1e-9}}
        gw = ServingGateway(make_engine(tiny_llama), port=0,
                            slo_targets=targets).start_in_thread()
        try:
            post(gw.port, {"prompt": [1, 2], "max_new_tokens": 2,
                           "stream": False})
            status, raw = get(gw.port, "/healthz")
            assert status == 200
            slo = json.loads(raw)["slo"]
            assert slo["ok"] is False
            assert slo["violations"] == ["ttft_p95_s"]
        finally:
            gw.stop_sync()


class TestServeLiveSnapshotter:
    def test_snapshot_fn_payload_shape(self, tiny_llama, tmp_path):
        """scripts/serve.py's SIGUSR1 snapshot payload: span tail +
        gateway gauges + per-tenant histograms + per-replica engine
        snapshots/histograms (the handler itself is PR 8 machinery,
        already signal-tested in tests/test_telemetry.py)."""
        import os
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        sys.path.insert(0, os.path.join(repo, "scripts"))
        import serve as serve_mod

        from scaletorch_tpu.telemetry.spans import SpanTracer

        tracer = SpanTracer(path=None, role="serve")
        gw = ServingGateway(make_engine(tiny_llama, tracer=tracer),
                            port=0, tracer=tracer).start_in_thread()
        try:
            post(gw.port, {"prompt": [1, 2], "max_new_tokens": 2,
                           "stream": False})
            args = serve_mod.parse_args(
                ["--telemetry_dir", str(tmp_path)])
            snapshotter = serve_mod.make_snapshotter(args, gw)
            payload = snapshotter.snapshot_fn()
            assert payload["gateway"]["http_requests_received"] == 1
            assert payload["tenant_histograms"]["e2e"]["default"][
                "count"] == 1
            replica = payload["replicas"]["r0"]
            assert replica["alive"] is True
            assert replica["histograms"]["ttft"]["count"] == 1
            assert payload["span_timeline_tail"]
            assert payload["slo"] is None
        finally:
            gw.stop_sync()


def sse_disconnect_after_first_token(port, body):
    """Raw-socket SSE client that walks away mid-stream."""
    payload = json.dumps(body).encode()
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    try:
        sock.sendall(
            b"POST /v1/generate HTTP/1.1\r\n"
            b"Host: x\r\nContent-Type: application/json\r\n"
            + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
        got = b""
        while b"event: token" not in got:
            chunk = sock.recv(4096)
            if not chunk:
                raise AssertionError(f"stream closed early: {got!r}")
            got += chunk
    finally:
        sock.close()  # mid-stream disconnect


class TestDisconnectReleasesPages:
    def test_mid_stream_disconnect_aborts_and_releases(self, tiny_llama):
        # room for a thousand tokens: on the tiny model a 25-token
        # stream could end ``ok`` before the disconnect was seen
        engine = make_engine(tiny_llama, max_slots=1, max_seq=1024)
        gw = ServingGateway(engine, port=0).start_in_thread()
        try:
            sse_disconnect_after_first_token(
                gw.port, {"prompt": [1, 2, 3, 4, 5],
                          "max_new_tokens": 1000, "stream": True})
            # the engine's thread counts the outcome and THEN drops the
            # slot's page references (``_retire_slot``): wait for both
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if gw.metrics.outcomes["aborted"] == 1 \
                        and engine.metrics.outcomes["aborted"] == 1 \
                        and not any(engine._slot_pages):
                    break
                time.sleep(0.02)
            assert gw.metrics.outcomes["aborted"] == 1
            assert engine.metrics.outcomes["aborted"] == 1
            # pages released: only radix-pinned prefix pages may remain,
            # and the allocator's books must balance exactly
            engine.allocator.check_conservation()
            for page, count in list(engine.allocator._ref.items()):
                assert count == 1, \
                    f"page {page} still slot-referenced after abort"
            # the freed slot keeps serving
            status, _, raw = post(
                gw.port, {"prompt": [9, 9], "max_new_tokens": 2,
                          "stream": False})
            assert status == 200
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()


class TestWorkerEdges:
    def test_submit_to_dead_worker_still_answers(self, tiny_llama):
        """The dispatcher's health check and the submit are not atomic:
        a closure enqueued into a dead worker's inbox must still be
        answered (rejected), never stranded."""
        from scaletorch_tpu.serving.gateway import EngineWorker

        worker = EngineWorker(make_engine(tiny_llama), replica_id="rX")
        worker.start()
        worker.shutdown(drain=True)
        worker.join(timeout=60)
        assert not worker.alive and worker.exit_code == 0
        done = []
        from scaletorch_tpu.serving.protocol import GenerateRequest

        worker.submit(GenerateRequest(prompt=[1, 2]),
                      lambda rid, toks, _t: None,
                      lambda result: done.append(result))
        assert len(done) == 1
        assert done[0].outcome == "rejected"

    def test_instant_disconnect_keeps_conservation(self, tiny_llama):
        """A client that closes its socket without reading ANY response
        bytes (before the SSE headers flush) must still leave exactly
        one recorded outcome — the write-failure path takes the same
        cancel/abort route as a mid-stream disconnect."""
        engine = make_engine(tiny_llama)
        gw = ServingGateway(engine, port=0).start_in_thread()
        try:
            payload = json.dumps({"prompt": [1, 2, 3],
                                  "max_new_tokens": 20,
                                  "stream": True}).encode()
            sock = socket.create_connection(("127.0.0.1", gw.port),
                                            timeout=30)
            sock.sendall(
                b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                + payload)
            sock.close()  # walk away before reading a single byte
            deadline = time.monotonic() + 30
            while (sum(gw.metrics.outcomes.values()) < 1
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            # a later request still works and the ledger balances
            status, _, _ = post(gw.port, {"prompt": [5], "stream": False,
                                          "max_new_tokens": 2})
            assert status == 200
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()
        engine.allocator.check_conservation()


class TestConservationProperty:
    """Acceptance: every accepted connection receives exactly one
    terminal status, and http_requests_received == sum(outcomes) across
    randomized storm/deadline/disconnect schedules."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_storm_deadline_disconnect_schedules(self, tiny_llama, seed):
        import random

        rng = random.Random(seed)
        engine = make_engine(tiny_llama, max_slots=2)
        gw = ServingGateway(
            engine, port=0, max_backlog=3,
            tenants={"flood": TenantConfig("flood", weight=1.0)},
        ).start_in_thread()
        statuses = []
        lock = threading.Lock()

        def one_request(i):
            kind = rng.random()
            tenant = rng.choice(["flood", "quiet", "default"])
            body = {"prompt": [1 + i % 8, 2, 3],
                    "max_new_tokens": rng.randint(1, 6),
                    "tenant": tenant}
            if kind < 0.2:
                body["ttl_s"] = 0.001  # near-certain timeout
            if kind >= 0.2 and kind < 0.35:
                try:
                    sse_disconnect_after_first_token(
                        gw.port, dict(body, stream=True,
                                      max_new_tokens=20))
                except (AssertionError, OSError):
                    pass
                return  # disconnects are recorded gateway-side
            body["stream"] = rng.random() < 0.5
            status, headers, raw = post(gw.port, body, timeout=120)
            if body["stream"] and status == 200:
                events = parse_sse_stream(raw)
                dones = [d for e, d in events if e == "done"]
                assert len(dones) == 1, "exactly one terminal per stream"
                status = STATUS_BY_OUTCOME[dones[0]["outcome"]]
            elif status == 429:
                assert "Retry-After" in headers
            with lock:
                statuses.append(status)

        try:
            threads = [threading.Thread(target=one_request, args=(i,))
                       for i in range(24)]
            # staggered storm: bursts + breathers
            for i, thread in enumerate(threads):
                thread.start()
                if rng.random() < 0.3:
                    time.sleep(0.03)
            for thread in threads:
                thread.join(timeout=180)
                assert not thread.is_alive(), "request hung"
        finally:
            gw.stop_sync()
        # every terminal status is one of the contract's statuses
        allowed = set(STATUS_BY_OUTCOME.values()) | {400}
        assert all(s in allowed for s in statuses), statuses
        gw.metrics.check_conservation()
        total = sum(gw.metrics.outcomes.values())
        assert total == gw.metrics.http_requests_received
        # the engine's own conservation held underneath
        engine_outcomes = sum(engine.metrics.outcomes.values())
        assert engine_outcomes == engine.metrics.requests_submitted
        engine.allocator.check_conservation()


class TestTenantFairnessE2E:
    def test_victim_tenant_served_within_weight_share(self, tiny_llama):
        """One tenant floods 8 requests ahead of the victim's 2; with
        equal weights the victim's requests complete well before the
        flood drains (FIFO would finish the entire flood first)."""
        engine = make_engine(tiny_llama, max_slots=1)
        gw = ServingGateway(engine, port=0).start_in_thread()
        order = []
        lock = threading.Lock()

        def run_one(tenant, i, n_tokens):
            status, _, _ = post(
                gw.port, {"prompt": [3, 1 + i],
                          "max_new_tokens": n_tokens,
                          "tenant": tenant, "stream": False}, timeout=300)
            with lock:
                order.append((tenant, status))

        try:
            # an occupier pins the single slot (and pays the first
            # compile) so every later arrival genuinely QUEUES — the
            # fairness decision happens in the gateway's WFQ, not in a
            # race against the engine draining the flood first
            occupier = threading.Thread(
                target=run_one, args=("flood", 0, 25))
            occupier.start()
            time.sleep(0.2)
            floods = [threading.Thread(target=run_one,
                                       args=("flood", i, 6))
                      for i in range(1, 8)]
            for thread in floods:
                thread.start()
            time.sleep(0.2)  # the flood queues first; victims arrive last
            victims = [threading.Thread(target=run_one,
                                        args=("victim", i, 6))
                       for i in range(2)]
            for thread in victims:
                thread.start()
            for thread in [occupier] + floods + victims:
                thread.join(timeout=300)
        finally:
            gw.stop_sync()
        assert all(status == 200 for _, status in order), order
        positions = [i for i, (tenant, _) in enumerate(order)
                     if tenant == "victim"]
        assert len(positions) == 2
        # WFQ interleaves the victim within its equal-weight share of
        # the remaining service; a FIFO gateway would park both victims
        # at positions 8 and 9 (after the entire flood)
        assert max(positions) <= 6, (positions, order)
        gw.metrics.check_conservation()


class TestGatewayDrills:
    def test_tenant_storm_drill(self, tiny_llama):
        injector = ServingFaultInjector(
            gw_tenant_storm_at=1, gw_tenant_storm_count=6)
        engine = make_engine(tiny_llama, max_slots=2)
        gw = ServingGateway(
            engine, port=0, injector=injector, max_backlog=4,
        ).start_in_thread()
        try:
            # arrival 1 triggers the storm; victim requests still finish
            for i in range(3):
                status, _, raw = post(
                    gw.port, {"prompt": [2 + i, 3], "max_new_tokens": 2,
                              "tenant": "victim", "stream": False},
                    timeout=120)
                assert status == 200, raw
        finally:
            gw.stop_sync()
        assert gw.metrics.injected_storm_requests == 6
        storm_total = sum(gw.metrics.storm_outcomes.values())
        assert storm_total == 6  # every storm request reached a terminal
        assert gw.metrics.storm_outcomes["shed"] > 0  # backlog cap bit
        gw.metrics.check_conservation()  # HTTP ledger unpolluted

    def test_replica_down_drill(self, tiny_llama):
        injector = ServingFaultInjector(gw_replica_down_at=1)
        engines = {"r0": make_engine(tiny_llama),
                   "r1": make_engine(tiny_llama)}
        gw = ServingGateway(
            engines, port=0, injector=injector).start_in_thread()
        try:
            status, _, raw = post(
                gw.port, {"prompt": [1, 2, 3], "max_new_tokens": 10,
                          "stream": True}, timeout=120)
            assert status == 200
            events = parse_sse_stream(raw)
            dones = [d for e, d in events if e == "done"]
            assert len(dones) == 1
            assert dones[0]["outcome"] == "aborted"  # died mid-stream
            # the survivor keeps serving; routing avoids the corpse
            for i in range(3):
                status, _, raw = post(
                    gw.port, {"prompt": [7 + i, 8], "max_new_tokens": 2,
                              "stream": False}, timeout=120)
                assert status == 200, raw
            snap = gw.router.snapshot()
            assert snap["router_replicas_dead"] == 1.0
            assert snap["router_replicas_alive"] == 1.0
            dead = [rid for rid, st in gw.router.replicas.items()
                    if not st.healthy][0]
            assert gw.workers[dead].exit_code == 44
            status, raw = get(gw.port, "/healthz")
            assert status == 200  # one survivor = still healthy
            assert json.loads(raw)["replicas"][dead]["alive"] is False
        finally:
            gw.stop_sync()
        gw.metrics.check_conservation()

    def test_injector_config_env_parity(self, monkeypatch):
        class Cfg:
            ft_gw_tenant_storm_at = 5
            ft_gw_tenant_storm_count = 9
            ft_gw_replica_down_at = 3

        inj = ServingFaultInjector.from_config(Cfg())
        assert inj.gw_tenant_storm_at == 5
        assert inj.gw_tenant_storm_count == 9
        assert inj.gw_replica_down_at == 3
        assert inj.active
        # present env wins over config
        monkeypatch.setenv("SCALETORCH_TPU_FT_GW_TENANT_STORM_AT", "2")
        inj = ServingFaultInjector.from_config(Cfg())
        assert inj.gw_tenant_storm_at == 2
        # explicit 0 CANCELS a config-armed drill (the restart contract)
        monkeypatch.setenv("SCALETORCH_TPU_FT_GW_TENANT_STORM_AT", "0")
        monkeypatch.setenv("SCALETORCH_TPU_FT_GW_REPLICA_DOWN_AT", "0")
        inj = ServingFaultInjector.from_config(Cfg())
        assert inj.gw_tenant_storm_at == 0
        assert inj.gw_replica_down_at == 0
        assert not inj.active

    def test_fires_once_at_exact_arrival(self):
        inj = ServingFaultInjector(gw_tenant_storm_at=3,
                                   gw_tenant_storm_count=4)
        assert inj.take_gw_tenant_storm(1) == 0
        assert inj.take_gw_tenant_storm(2) == 0
        assert inj.take_gw_tenant_storm(3) == 4
        assert inj.take_gw_tenant_storm(3) == 0  # fires once
        inj2 = ServingFaultInjector(gw_replica_down_at=2)
        assert not inj2.take_gw_replica_down(1)
        assert inj2.take_gw_replica_down(2)
        assert not inj2.take_gw_replica_down(2)


class TestDrain:
    def test_stop_drains_in_flight_and_aborts_queued(self, tiny_llama):
        engine = make_engine(tiny_llama, max_slots=1)
        gw = ServingGateway(engine, port=0).start_in_thread()
        results = {}
        lock = threading.Lock()

        def run_one(name, n_tokens):
            status, _, raw = post(
                gw.port, {"prompt": [1, 2], "max_new_tokens": n_tokens,
                          "stream": False}, timeout=120)
            with lock:
                results[name] = (status, json.loads(raw))

        in_flight = threading.Thread(target=run_one, args=("active", 20))
        queued = threading.Thread(target=run_one, args=("queued", 20))
        in_flight.start()
        time.sleep(0.5)  # let it dispatch and start decoding
        queued.start()
        deadline = time.monotonic() + 30
        while (gw.metrics.http_requests_received < 2
               and time.monotonic() < deadline):
            time.sleep(0.02)  # both requests must be IN before the drain
        gw.stop_sync(drain=True)
        in_flight.join(timeout=60)
        queued.join(timeout=60)
        assert results["active"][0] == 200
        assert results["active"][1]["outcome"] == "ok"
        assert len(results["active"][1]["token_ids"]) == 20
        assert results["queued"][1]["outcome"] in ("aborted", "ok")
        # post-drain: the worker exited cleanly, pools balance
        assert gw.workers["r0"].exit_code == 0
        engine.allocator.check_conservation()
        gw.metrics.check_conservation()
        # a post-drain arrival is refused, not hung
        status, _, raw = None, None, None
        try:
            status, _, raw = post(
                gw.port, {"prompt": [1], "max_new_tokens": 1}, timeout=5)
        except (urllib.error.URLError, OSError):
            pass  # socket closed entirely — equally correct
        if status is not None:
            assert status in (503, 429)

#!/usr/bin/env python
"""Gateway smoke: boot scripts/serve.py, stream one SSE request, verify.

CPU ONLY, by its shape: the server is a child process (pinned to
``JAX_PLATFORMS=cpu`` below) and THIS process rebuilds the same engine
as the bit-parity oracle — on a TPU a chip belongs to one process, so
the parent could never get the device its child holds. The on-chip
counterpart is ``chip_smoke.py`` at the repo root, whose client never
imports jax.

The CI ``gateway-smoke`` step (tier1.yml) runs this end to end on a CPU
mesh:

  1. boot ``scripts/serve.py --preset tiny`` as a real subprocess
     (with ``--telemetry_dir`` + ``--slo_path``) and wait for its
     ``READY port=<p>`` line;
  2. stream one greedy request over HTTP via urllib (SSE), carrying a
     W3C ``traceparent`` header with a KNOWN trace id;
  3. rebuild the SAME deterministic tiny engine in-process (same
     ``--param_seed``) and assert the streamed tokens equal the direct
     ``InferenceEngine`` run BIT-FOR-BIT (the acceptance oracle: the
     gateway adds transport, never arithmetic);
  4. scrape ``/healthz`` (live SLO verdict) and ``/metrics``
     (tenant-labeled histogram series; the scrape is saved for the CI
     artifact + slo_check);
  5. SIGTERM the server and assert it drains to exit code 0 (the
     exit-code contract's clean drain);
  6. post-mortem the telemetry artifacts: the Chrome trace must hold
     the request's spans on BOTH the gateway thread and the engine
     worker thread correlated by the trace id we sent (plus the tick
     loop's phase spans), the access JSONL must carry the request's
     record, and ``tools/slo_check.py`` must accept the JSONL AND the
     /metrics scrape against the ``tiny`` SLO preset.

Artifacts land in ``$GATEWAY_SMOKE_TELEMETRY`` (default
``/tmp/gateway-smoke``) — CI uploads them and runs the slo_check gate
on them again as a separate blocking step.

Exit 0 = all green; any assertion prints a diagnostic and exits 1.

``--procs N`` (the CI ``gateway-smoke-mp`` step) switches to the
PROCESS-FLEET drill instead: boot ``serve.py --serve_replica_procs N``
with ``--ft_gw_replica_crash_at 1`` armed, so the replica serving the
FIRST request is SIGKILLed mid-stream — the stream must still end in
exactly one terminal (``aborted``), the supervisor must restart the
child (new pid on ``/healthz``, ``replica_restarts_total`` bumped), a
follow-up request must stream bit-identical tokens to the direct
engine, the /metrics ledger must balance THROUGH the crash
(``http_requests_received == sum(outcomes)``). Then the WARM-REJOIN
drill: the healed request left prefix pages on one replica (the
donor), so a second kill -9 of the OTHER replica must come back
WARMED — the supervisor restarts it, the gateway pulls the donor's
frozen prefix pages peer-to-peer concurrent with readiness,
``/healthz`` reports the transferred pages, and the FIRST post-restart
shared-prefix request records a prefix HIT with bit-identical tokens
and zero retraces (``engine_decode_compile_count == 1`` fleet-wide).
SIGTERM must drain the whole fleet to exit 0, and the supervisor's
JSONL event stream (spawn/ready/crash/restart) plus the ``warmup``
record plus slo_check must hold on the artifacts.

``--disagg`` (the CI ``gateway-smoke-disagg`` step) runs the single-
process smoke against ``serve.py --disagg 4:4`` — the disaggregated
prefill/decode engine (inference/disagg.py) on an 8-virtual-device CPU
mesh. The parity oracle stays the COLOCATED engine (``--disagg`` is
stripped from the oracle's args), so the assertion is the ISSUE 19
acceptance itself: MPMD slices + page handoff add transport, never
arithmetic. On top of the standard checks, ``/healthz`` must carry the
per-slice ``disagg`` block, ``/metrics`` the per-slice busy-fraction
gauges + ``handoff_seconds`` histogram, and the Chrome trace the
``req.handoff`` lifecycle span next to the ``handoff`` tick phase.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

from scaletorch_tpu.serving.protocol import parse_metrics_text  # noqa: E402

PROMPT = [1, 2, 3, 5, 8]
MAX_NEW = 12
SEED = 7
TELEMETRY_DIR = os.environ.get("GATEWAY_SMOKE_TELEMETRY",
                               "/tmp/gateway-smoke")
TRACE_ID = "0af7651916cd43dd8448eb211c80319c"
PARENT_SPAN = "b7ad6b7169203331"
SERVE_ARGS = [
    "--preset", "tiny", "--param_seed", str(SEED),
    "--max_slots", "2", "--max_seq", "64", "--prefill_len", "16",
    "--page_size", "4",
    "--serve_port", "0",
    "--telemetry_dir", TELEMETRY_DIR,
    "--slo_path", os.path.join(REPO, "tools", "slo.json"),
    "--slo_preset", "tiny",
]


def pump_output(proc: subprocess.Popen) -> "queue.Queue":
    """Echo the child's stdout from a reader thread so the deadline in
    ``wait_ready`` stays real — a wedged server that prints nothing must
    FAIL at the timeout, not hang CI on a blocking readline."""
    lines: "queue.Queue" = queue.Queue()

    def _pump() -> None:
        for line in proc.stdout:
            sys.stdout.write(f"[serve] {line}")
            sys.stdout.flush()
            lines.put(line)
        lines.put(None)  # EOF

    threading.Thread(target=_pump, daemon=True).start()
    return lines


def wait_ready(lines: "queue.Queue", proc: subprocess.Popen,
               timeout_s: float = 120.0) -> int:
    """Watch the pumped stdout until ``READY port=<p>``."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            line = lines.get(timeout=1.0)
        except queue.Empty:
            continue
        if line is None:
            raise AssertionError(
                f"server exited early (rc={proc.poll()})")
        if line.startswith("READY port="):
            return int(line.strip().split("=", 1)[1])
    raise AssertionError(f"server never printed READY in {timeout_s:g}s")


def direct_engine_tokens() -> list:
    """The oracle: the same deterministic engine, no HTTP in sight."""
    import serve as serve_mod

    args = serve_mod.parse_args(SERVE_ARGS)
    cfg, params = serve_mod.build_model(args)
    engine = serve_mod.build_engine(args, cfg, params)
    rid = engine.submit(PROMPT, max_new_tokens=MAX_NEW)
    return engine.run()[rid].tokens


def check_trace_correlation(trace_path: str, *,
                            disagg: bool = False) -> None:
    """Acceptance: ONE Perfetto-loadable trace in which the request's
    spans on the gateway (asyncio) thread and the engine worker thread
    are correlated by the trace id we sent, next to the tick loop's
    phase spans."""
    from scaletorch_tpu.telemetry.spans import load_trace

    events = load_trace(trace_path)
    ours = [e for e in events if e.get("id") == TRACE_ID]
    names = {e["name"] for e in ours}
    gw_names = {"gw.request", "gw.queued", "gw.stream"}
    engine_names = {"request", "req.queued", "req.prefill", "req.decode",
                    "req.finalize"}
    if disagg:
        # the handoff seam must be visible on the request's lifeline
        engine_names = engine_names | {"req.handoff"}
    assert gw_names <= names, f"missing gateway spans: {gw_names - names}"
    assert engine_names <= names, \
        f"missing engine lifecycle spans: {engine_names - names}"
    gw_tids = {e["tid"] for e in ours if e["name"] in gw_names}
    engine_tids = {e["tid"] for e in ours if e["name"] in engine_names}
    assert gw_tids and engine_tids and not (gw_tids & engine_tids), (
        "request spans did not cross threads: gateway tids "
        f"{gw_tids}, engine tids {engine_tids}")
    tick_spans = {e["name"] for e in events
                  if e.get("ph") == "X" and e.get("tid") in engine_tids}
    want_ticks = {"engine.tick", "engine.tick.decode",
                  "engine.tick.prefill"}
    if disagg:
        want_ticks = want_ticks | {"handoff"}
    assert want_ticks <= tick_spans, (
        f"engine tick-loop phase spans missing on the worker thread: "
        f"{tick_spans}")
    outcome = [e for e in ours
               if e["name"] == "req.finalize"][0]["args"]["outcome"]
    assert outcome == "ok", outcome
    print(f"[smoke] trace correlation OK: {len(ours)} request events "
          f"across tids {sorted(gw_tids | engine_tids)}")


def check_access_log(events_path: str) -> None:
    access = [json.loads(line) for line in open(events_path)
              if '"access"' in line]
    access = [e for e in access if e.get("kind") == "access"]
    assert len(access) == 1, f"want exactly one access record: {access}"
    rec = access[0]
    assert rec["v"] == 1 and rec["trace_id"] == TRACE_ID, rec
    assert rec["tenant"] == "default" and rec["outcome"] == "ok", rec
    assert rec["status"] == 200 and rec["replica"] == "r0", rec
    assert rec["tokens"] == MAX_NEW, rec
    assert rec["ttft_s"] > 0 and rec["e2e_s"] >= rec["ttft_s"], rec
    assert rec["prefix_hit"] is False, rec
    print("[smoke] access record OK")


def run_slo_check(events_path: str, prom_path: str) -> None:
    for extra in ([events_path], ["--prom", prom_path]):
        cmd = [sys.executable, os.path.join(REPO, "tools", "slo_check.py"),
               "--slo", os.path.join(REPO, "tools", "slo.json"),
               "--preset", "tiny", *extra]
        out = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
        sys.stdout.write(out.stdout)
        assert out.returncode == 0, (
            f"slo_check {extra} failed rc={out.returncode}:\n"
            f"{out.stdout}{out.stderr}")
    print("[smoke] slo_check OK (JSONL + /metrics scrape)")


def stream_generate(base: str, *, timeout: float = 120.0):
    """POST one streaming request with the known traceparent; return
    (events, streamed_tokens, dones, traceparent_echo)."""
    from scaletorch_tpu.serving.protocol import (
        parse_sse_stream,
        stream_tokens,
    )

    body = json.dumps({"prompt": PROMPT, "max_new_tokens": MAX_NEW,
                       "stream": True}).encode()
    request = urllib.request.Request(
        f"{base}/v1/generate", data=body, method="POST")
    request.add_header("traceparent", f"00-{TRACE_ID}-{PARENT_SPAN}-01")
    response = urllib.request.urlopen(request, timeout=timeout)
    echo = response.headers.get("traceparent", "")
    events = parse_sse_stream(response.read())
    dones = [d for e, d in events if e == "done"]
    return events, stream_tokens(events), dones, echo


def main_mp(procs: int) -> int:
    """The process-fleet drill: kill -9 mid-stream, survive, heal."""
    if os.path.isdir(TELEMETRY_DIR):
        shutil.rmtree(TELEMETRY_DIR)
    os.makedirs(TELEMETRY_DIR, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         *SERVE_ARGS,
         "--serve_replica_procs", str(procs),
         "--ft_gw_replica_crash_at", "1",
         "--supervisor_backoff_base_s", "0.2",
         "--supervisor_backoff_max_s", "1.0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    try:
        lines = pump_output(proc)
        port = wait_ready(lines, proc, timeout_s=300.0)
        base = f"http://127.0.0.1:{port}"

        health = json.loads(
            urllib.request.urlopen(f"{base}/healthz", timeout=30).read())
        pids_before = {rid: rep["pid"]
                       for rid, rep in health["replicas"].items()}
        assert len(pids_before) == procs, health
        assert all(isinstance(p, int) for p in pids_before.values()), \
            health

        # 1. the armed drill SIGKILLs the serving replica mid-stream:
        #    the stream must still end in EXACTLY ONE terminal
        _, streamed, dones, _ = stream_generate(base)
        assert len(dones) == 1, f"want exactly one done event: {dones}"
        assert dones[0]["outcome"] == "aborted", dones[0]
        assert streamed == dones[0]["token_ids"], (streamed, dones[0])
        print("[smoke-mp] kill -9 mid-stream -> exactly one terminal "
              f"(aborted, {len(streamed)} partial tokens) OK")

        # 2. the supervisor restarts the victim: new pid, counter bumped
        deadline = time.monotonic() + 300
        victim = None
        while time.monotonic() < deadline:
            health = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=30).read())
            restarted = {
                rid: rep for rid, rep in health["replicas"].items()
                if rep.get("restarts_total", 0) >= 1
                and rep.get("state") == "up"}
            if restarted:
                victim = next(iter(restarted))
                break
            time.sleep(0.5)
        assert victim is not None, f"no replica restarted: {health}"
        rep = health["replicas"][victim]
        assert rep["pid"] != pids_before[victim], (rep, pids_before)
        assert rep["last_exit_code"] not in (None, 0), rep
        print(f"[smoke-mp] supervisor restarted {victim}: "
              f"pid {pids_before[victim]} -> {rep['pid']}, "
              f"exit {rep['last_exit_code']} OK")

        # 3. the healed fleet streams BIT-IDENTICAL tokens
        _, streamed, dones, echo = stream_generate(base)
        assert len(dones) == 1 and dones[0]["outcome"] == "ok", dones
        assert echo.startswith(f"00-{TRACE_ID}-"), echo
        reference = direct_engine_tokens()
        assert streamed == reference, (
            f"post-restart stream diverged:\n"
            f"  streamed:  {streamed}\n  reference: {reference}")
        print(f"[smoke-mp] post-restart SSE bit-parity OK over "
              f"{len(streamed)} tokens")

        # 4. the ledger balances THROUGH the crash
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=30).read().decode()
        prom = parse_metrics_text(metrics)
        received = prom["scaletorch_http_requests_received"]
        outcome_sum = sum(
            v for k, v in prom.items()
            if k.startswith("scaletorch_http_")
            and k.split("scaletorch_http_", 1)[1] in (
                "ok", "timeout", "shed", "rejected", "quarantined",
                "aborted"))
        assert received == 2.0, received
        assert outcome_sum == received, (outcome_sum, received, prom)
        assert prom["scaletorch_http_aborted"] == 1.0, prom
        assert prom["scaletorch_http_ok"] == 1.0, prom
        restarts = [v for k, v in prom.items()
                    if k.startswith("scaletorch_replica_restarts_total")]
        assert restarts and sum(restarts) >= 1.0, prom
        ups = [v for k, v in prom.items()
               if k.startswith("scaletorch_replica_up")]
        assert len(ups) == procs and all(u == 1.0 for u in ups), prom
        print("[smoke-mp] conservation through the crash OK "
              f"(received={received:g} == outcomes={outcome_sum:g}; "
              f"restarts={sum(restarts):g})")

        # 5. warm rejoin: request 2 left prefix pages on ONE replica
        #    (the donor); kill -9 the OTHER — the supervisor restarts
        #    it and the gateway warms it peer-to-peer, concurrent with
        #    readiness, so /healthz must show the transferred pages
        deadline = time.monotonic() + 120
        donor = None
        while time.monotonic() < deadline:
            health = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=30).read())
            donors = [rid for rid, rep in health["replicas"].items()
                      if (rep.get("prefix_pages") or 0) > 0]
            if donors:
                donor = donors[0]
                break
            time.sleep(0.25)
        assert donor is not None, f"no replica registered prefix " \
            f"pages after request 2: {health}"
        victim2 = next(rid for rid in sorted(health["replicas"])
                       if rid != donor)
        rep2 = health["replicas"][victim2]
        restarts_before = rep2["restarts_total"]
        os.kill(rep2["pid"], signal.SIGKILL)
        deadline = time.monotonic() + 300
        warmed = None
        while time.monotonic() < deadline:
            health = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=30).read())
            rep = health["replicas"][victim2]
            if rep.get("state") == "up" \
                    and rep.get("restarts_total", 0) > restarts_before \
                    and (rep.get("warm_pages") or 0) > 0:
                warmed = rep
                break
            time.sleep(0.5)
        assert warmed is not None, (
            f"restarted {victim2} never reported warmed pages: {health}")
        print(f"[smoke-mp] warm rejoin OK: {victim2} restarted with "
              f"{warmed['warm_pages']:g} pages pulled from {donor}")

        # 6. FIRST post-restart shared-prefix request: the router's
        #    learned ownership sends it to the warmed replica, which
        #    serves a prefix HIT with bit-identical tokens
        _, streamed, dones, _ = stream_generate(base)
        assert len(dones) == 1 and dones[0]["outcome"] == "ok", dones
        assert streamed == reference, (
            f"warmed-replica stream diverged:\n"
            f"  streamed:  {streamed}\n  reference: {reference}")
        print("[smoke-mp] warmed-replica SSE bit-parity OK over "
              f"{len(streamed)} tokens")

        # 7. the ledger balances THROUGH the warm cycle, the warm
        #    metric families are live, and neither engine retraced
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=30).read().decode()
        prom = parse_metrics_text(metrics)
        received = prom["scaletorch_http_requests_received"]
        assert received == 3.0, received
        assert prom["scaletorch_http_aborted"] == 1.0, prom
        assert prom["scaletorch_http_ok"] == 2.0, prom
        warm_key = (f'scaletorch_replica_warm_pages_total'
                    f'{{replica="{victim2}"}}')
        assert prom.get(warm_key, 0.0) >= 1.0, (warm_key, prom)
        assert "scaletorch_warm_transfer_seconds" in metrics, \
            metrics[:400]
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            compiles = [
                v for k, v in parse_metrics_text(urllib.request.urlopen(
                    f"{base}/metrics", timeout=30).read().decode()
                ).items()
                if k.startswith("scaletorch_engine_decode_compile_count")]
            if len(compiles) == procs and all(c == 1.0 for c in compiles):
                break
            time.sleep(0.5)
        assert len(compiles) == procs and all(c == 1.0 for c in compiles), (
            f"warming must not retrace: decode compile counts {compiles}")
        prom_path = os.path.join(TELEMETRY_DIR, "metrics_scrape.txt")
        with open(prom_path, "w") as f:
            f.write(metrics)
        print("[smoke-mp] conservation + one-compile through the warm "
              f"cycle OK (received={received:g})")

        # 8. SIGTERM drains the WHOLE fleet to exit 0
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=180)
        assert rc == 0, f"drain exit code {rc}, want 0"
        print("[smoke-mp] SIGTERM fleet drain exit 0 OK")

        # 9. post-mortem: supervisor JSONL events + warmup + access +
        #    slo gates
        events_path = os.path.join(TELEMETRY_DIR, "gateway_events.jsonl")
        records = [json.loads(line) for line in open(events_path)]
        sup_events = [r["event"] for r in records
                      if r.get("kind") == "supervisor"]
        for needed in ("spawn", "ready", "crash", "restart"):
            assert needed in sup_events, (needed, sup_events)
        warmups = [r for r in records if r.get("kind") == "warmup"]
        assert any(r["replica"] == victim2 and r["status"] == "warmed"
                   and r["pages"] >= 1 and r["donor"] == donor
                   for r in warmups), warmups
        access = [r for r in records if r.get("kind") == "access"]
        assert len(access) == 3, access
        assert sorted(r["outcome"] for r in access) == \
            ["aborted", "ok", "ok"], access
        # the warmed replica's FIRST request hit the transferred prefix
        assert any(r["outcome"] == "ok" and r["replica"] == victim2
                   and r["prefix_hit"] is True for r in access), access
        print(f"[smoke-mp] supervisor + warmup event streams OK "
              f"({sup_events})")
        run_slo_check(events_path, prom_path)
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def main(disagg: bool = False) -> int:
    if os.path.isdir(TELEMETRY_DIR):
        shutil.rmtree(TELEMETRY_DIR)  # stale artifacts must not pass
    os.makedirs(TELEMETRY_DIR, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # serve.py self-provisions the 8-virtual-device CPU mesh for
    # --disagg; the ORACLE below deliberately stays colocated (base
    # SERVE_ARGS), so parity is asserted across the architecture split
    serve_args = SERVE_ARGS + (["--disagg", "4:4"] if disagg else [])
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "scripts", "serve.py"),
         *serve_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    try:
        lines = pump_output(proc)
        port = wait_ready(lines, proc)
        base = f"http://127.0.0.1:{port}"

        body = json.dumps({"prompt": PROMPT, "max_new_tokens": MAX_NEW,
                           "stream": True}).encode()
        request = urllib.request.Request(
            f"{base}/v1/generate", data=body, method="POST")
        request.add_header("traceparent",
                           f"00-{TRACE_ID}-{PARENT_SPAN}-01")
        response = urllib.request.urlopen(request, timeout=120)
        echo = response.headers.get("traceparent", "")
        raw = response.read()
        from scaletorch_tpu.serving.protocol import (
            parse_sse_stream,
            stream_tokens,
        )

        events = parse_sse_stream(raw)
        streamed = stream_tokens(events)
        dones = [d for e, d in events if e == "done"]
        assert len(dones) == 1, f"expected exactly one done event: {events}"
        assert dones[0]["outcome"] == "ok", dones[0]
        assert streamed == dones[0]["token_ids"], (streamed, dones[0])
        # the trace id we sent round-tripped: response header + terminal
        assert echo.startswith(f"00-{TRACE_ID}-"), echo
        assert dones[0]["trace_id"] == TRACE_ID, dones[0]

        reference = direct_engine_tokens()
        assert streamed == reference, (
            f"SSE stream diverged from the direct engine:\n"
            f"  streamed:  {streamed}\n  reference: {reference}")
        print(f"[smoke] SSE bit-parity OK over {len(streamed)} tokens "
              f"(traceparent round-tripped)")

        health = json.loads(
            urllib.request.urlopen(f"{base}/healthz", timeout=30).read())
        assert health["status"] == "ok", health
        assert health["slo"]["ok"] is True, health["slo"]
        assert health["slo"]["requests"] == 1, health["slo"]
        if disagg:
            # per-slice state must be live on /healthz
            dis = health["replicas"]["r0"].get("disagg")
            assert dis is not None, health["replicas"]
            assert dis["prefill_slice"]["devices"] == 4, dis
            assert dis["decode_slice"]["devices"] == 4, dis
            assert dis["handoffs"] >= 1, dis
            assert dis["handoff_failures"] == 0, dis
            assert dis["pages_handed_off"] >= 1, dis
            print(f"[smoke] /healthz disagg block OK "
                  f"({dis['handoffs']:g} handoffs, "
                  f"{dis['pages_handed_off']:g} pages)")
        metrics = urllib.request.urlopen(
            f"{base}/metrics", timeout=30).read().decode()
        assert "scaletorch_http_requests_received 1.0" in metrics, \
            metrics[:400]
        # tenant-labeled histogram series (labels sort le < tenant)
        needles = [
            "# TYPE scaletorch_request_ttft_seconds histogram",
            'scaletorch_request_ttft_seconds_count{tenant="default"} 1',
            "scaletorch_request_tpot_seconds_bucket{le=",
            'scaletorch_request_queue_wait_seconds_count'
            '{tenant="default"} 1',
            'scaletorch_engine_pages_in_use{replica="r0"}',
        ]
        if disagg:
            needles += [
                'scaletorch_engine_prefill_slice_busy_fraction'
                '{replica="r0"}',
                'scaletorch_engine_decode_slice_busy_fraction'
                '{replica="r0"}',
                'scaletorch_engine_pages_handed_off{replica="r0"}',
                "# TYPE scaletorch_handoff_seconds histogram",
                'scaletorch_handoff_seconds_count{replica="r0"} 1',
            ]
        for needle in needles:
            assert needle in metrics, f"missing {needle}"
        prom_path = os.path.join(TELEMETRY_DIR, "metrics_scrape.txt")
        with open(prom_path, "w") as f:
            f.write(metrics)
        print("[smoke] /healthz (SLO ok) + /metrics histogram series OK")

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)  # the pump thread echoes the tail
        assert rc == 0, f"drain exit code {rc}, want 0"
        print("[smoke] SIGTERM drain exit 0 OK")

        check_trace_correlation(
            os.path.join(TELEMETRY_DIR, "serve.trace.json"),
            disagg=disagg)
        events_path = os.path.join(TELEMETRY_DIR, "gateway_events.jsonl")
        check_access_log(events_path)
        run_slo_check(events_path, prom_path)
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--procs", type=int, default=0,
                    help="N >= 2: run the process-fleet crash drill "
                         "(serve.py --serve_replica_procs N) instead of "
                         "the single-process smoke.")
    ap.add_argument("--disagg", action="store_true",
                    help="Run the single-process smoke against "
                         "serve.py --disagg 4:4 (disaggregated prefill/"
                         "decode slices); the parity oracle stays "
                         "colocated.")
    cli = ap.parse_args()
    if cli.procs > 0 and cli.disagg:
        ap.error("--disagg is in-process only (no --procs)")
    sys.exit(main_mp(cli.procs) if cli.procs > 0
             else main(disagg=cli.disagg))

"""Runner of the serving cells: gateway -> one EngineWorker -> paged
engine on one chip, load from a client process over HTTP.

The system under test is built by the program's own pieces
(``scripts/serve.py``'s ``parse_args``/``build_engine`` and
``ServingGateway``, as ``build_gateway`` wires them). The benchmark
owns: the weights (one jitted call of the program's own initialiser
for the model it built from the configuration, from ``--seed``, in the
serving dtype), the reference check through the
engine's own paged prefill and decode steps (which is also the
warm-up of both shapes), the client process, the clocks and the trace.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.lib import modules, program
from benchmarks.lib import trace as trace_lib
from benchmarks.lib import traffic as traffic_lib

# keys of a cell's ``check`` that are the runner's own; every other key
# is a size of the reference and goes to its factory as it is
RUNNER_CHECK_KEYS = ("prompts", "decode_positions", "rtol_of_max")

# from the client process's launch to its first request: time for it to
# import, read its job and open its sockets
LOADGEN_START_S = 1.0


class Records:
    """An exporter for the gateway that keeps its records in memory."""

    def __init__(self) -> None:
        self.by_kind: Dict[str, List[Dict[str, Any]]] = {}
        self._lock = threading.Lock()

    def emit(self, kind: str, record: Dict[str, Any]) -> None:
        with self._lock:
            self.by_kind.setdefault(kind, []).append(
                dict(record, emitted_t=time.monotonic()))

    def close(self) -> None:
        pass


class Annotated:
    """A callable that runs under a host span in the profiler's trace
    and is otherwise the callable it wraps (attributes forwarded)."""

    def __init__(self, fn, name: str, tracer) -> None:
        self._fn, self._name, self._tracer = fn, name, tracer

    def __call__(self, *args, **kwargs):
        with self._tracer.span(self._name):
            return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


def load_serve_module(root: str):
    path = os.path.join(root, "scripts", "serve.py")
    spec = importlib.util.spec_from_file_location("_bench_serve", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_params(init, cfg, seed: int):
    """The program's own initialiser, as one jitted call on the device,
    in the dtype the weights are served in."""
    import jax

    return jax.jit(init, static_argnums=1)(
        jax.random.PRNGKey(traffic_lib.fold_seed(seed)), cfg)


def reference_logits(reference, config, check: Dict[str, Any], params,
                     tokens: np.ndarray, lens: np.ndarray,
                     decode_positions: int, wrong=None):
    """Reference logits [n, decode_positions + 1, V] at rows
    ``len - 1 .. len - 1 + decode_positions`` of each sequence. The
    prompt buffer is padded to a multiple of the check's ``q_block``
    (the one reference size the runner reads too); every size of the
    check goes to the reference as it is."""
    import jax.numpy as jnp

    q_block = int(check.get("q_block", 1))
    width = tokens.shape[1]
    padded = -(-width // q_block) * q_block
    tokens = np.pad(tokens, ((0, 0), (0, padded - width)))
    rows = (lens[:, None] - 1
            + np.arange(decode_positions + 1)[None, :]).astype(np.int32)
    fn = reference.make_logits_fn(
        config, wrong=wrong, **modules.check_sizes(check, RUNNER_CHECK_KEYS))
    return fn(params, jnp.asarray(tokens), jnp.asarray(rows))


def system_logit_errors(engine, tokens: np.ndarray, lens: np.ndarray,
                        decode_positions: int, ref_logits) -> Dict[str, float]:
    """Prefill and ``decode_positions`` teacher-forced decode steps
    through the engine's own jitted paged steps (its params, its pool,
    identity page tables: slot b owns pages b*P+1 ..), each step's
    logits against the reference's. Largest error, largest reference
    magnitude, all-finite flag."""
    import jax
    import jax.numpy as jnp

    n = len(lens)
    slots, pps = engine.max_slots, engine._pages_per_slot
    if n > slots:
        raise ValueError(f"{n} check prompts over {slots} slots")
    tables = np.zeros((slots, pps), np.int32)
    tables[:n] = (np.arange(n * pps, dtype=np.int32) + 1).reshape(n, pps)
    tables = jnp.asarray(tables)
    base_keys = jnp.asarray(np.zeros((slots, 2), np.uint32))
    active = np.zeros(slots, bool)
    active[:n] = True

    @jax.jit
    def error(system, reference):
        diff = jnp.abs(system[:n].astype(jnp.float32) - reference)
        return (jnp.max(diff), jnp.max(jnp.abs(reference)),
                jnp.all(jnp.isfinite(system[:n])))

    buf = np.zeros((slots, engine.prefill_len), np.int32)
    tail = np.ones(slots, np.int32)
    for i in range(n):
        buf[i, : lens[i]] = tokens[i, : lens[i]]
        tail[i] = lens[i]
    results = []
    with engine.on_device():
        _first, logits, _finite, engine.cache = engine._prefill(
            engine.params, jnp.asarray(buf), jnp.asarray(tail),
            jnp.asarray(np.zeros(slots, np.int32)), jnp.asarray(active),
            tables, engine.cache, base_keys)
        results.append(error(logits, ref_logits[:, 0]))
        for t in range(decode_positions):
            feed = np.zeros(slots, np.int32)
            positions = np.zeros(slots, np.int32)
            for i in range(n):
                feed[i] = tokens[i, lens[i] + t]
                positions[i] = lens[i] + t
            _next, logits, _finite, engine.cache = engine._decode(
                engine.params, jnp.asarray(feed), jnp.asarray(positions),
                jnp.asarray(active), tables, engine.cache, base_keys)
            results.append(error(logits, ref_logits[:, t + 1]))
    errs = [(float(a), float(b), bool(c)) for a, b, c in results]
    return {"max_abs_err": max(e[0] for e in errs),
            "max_abs_reference": max(e[1] for e in errs),
            "prefill_max_abs_err": errs[0][0],
            "all_finite": all(e[2] for e in errs)}


def build_engine(ctx, cfg, params):
    import jax

    serve = load_serve_module(ctx["root"])
    shape = ctx["config"]["serve"]
    flags = ["--max_slots", str(shape["max_slots"]),
             "--max_seq", str(shape["max_seq"]),
             "--prefill_len", str(shape["prefill_len"]),
             "--cache_layout", "paged",
             "--page_size", str(shape["page_size"]),
             "--serve_port", "0"]
    for key, value in ctx["workload"].get("launch", {}).items():
        flags += [f"--{key}", str(value)]
    args = serve.parse_args(flags)
    engine = serve.build_engine(args, cfg, params, device=jax.devices()[0])
    return args, engine


def build_gateway(args, engine, records: Records):
    """``scripts/serve.py`` ``build_gateway``'s wiring, around an
    engine that exists already."""
    from scaletorch_tpu.serving.admission import parse_tenant_spec
    from scaletorch_tpu.serving.gateway import ServingGateway

    return ServingGateway(
        {"r0": engine}, host=args.serve_host, port=args.serve_port,
        tenants=parse_tenant_spec(args.serve_tenants),
        default_weight=args.serve_default_weight,
        max_backlog=args.serve_max_backlog,
        free_page_watermark=args.serve_free_page_watermark,
        default_ttl_s=args.serve_default_ttl_s,
        exporter=records)


def start_loadgen(job: Dict[str, Any]):
    """The client process and the thread that feeds and drains it."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "loadgen.py")
    proc = subprocess.Popen([sys.executable, path], stdin=subprocess.PIPE,
                            stdout=subprocess.PIPE)
    box: Dict[str, Any] = {}

    def pump():
        out, _ = proc.communicate(json.dumps(job).encode())
        box["out"] = out

    thread = threading.Thread(target=pump, name="loadgen-pipe", daemon=True)
    thread.start()
    return proc, thread, box


def live_tokens_mean(records: List[Dict[str, Any]], start: float,
                     stop: float, step_s: float = 0.05) -> Optional[float]:
    """Time-average over [start, stop] of the tokens the engine holds
    in its cache for the requests streaming at that instant (prompt +
    tokens delivered so far), as the client sees it."""
    if stop <= start:
        return None
    sums = []
    for t in np.arange(start, stop, step_s):
        live = 0
        for r in records:
            times = r["token_times"]
            if times and times[0] <= t < (r["end_t"] or math.inf):
                live += r["prompt_tokens"] + int(
                    np.searchsorted(times, t, side="right"))
        sums.append(live)
    return float(np.mean(sums)) if sums else None


def client_metrics(records: List[Dict[str, Any]], t0: float, t1: float,
                   give_up_s: float) -> Dict[str, Any]:
    """End-to-end numbers from the client's clock. TTFT over measured
    requests due in the window, from the due instant (a request with no
    first token counts as ``give_up_s``); inter-token gaps that end
    inside the window, over all streams; tokens delivered inside it."""
    ttft, gaps, delivered = [], [], 0
    for r in records:
        times, counts = r["token_times"], r["token_counts"]
        if r["measured"] and t0 <= r["due_t"] < t1:
            r["ttft_s"] = (times[0] - r["due_t"]) if times else None
            ttft.append(r["ttft_s"] if times else give_up_s)
        if r["send_t"] is not None:
            r["late_s"] = r["send_t"] - r["due_t"]
        for i, (t, k) in enumerate(zip(times, counts)):
            if t0 <= t < t1:
                delivered += k
                if i > 0:
                    gaps.append(t - times[i - 1])
                gaps.extend([0.0] * (k - 1))
    streaming = sum(1 for r in records
                    if r["token_times"] and r["token_times"][0] < t1
                    and (r["end_t"] or t1) > t0)
    out: Dict[str, Any] = {
        "serve_tokens_per_s": delivered / (t1 - t0),
        "n_ttft": len(ttft), "n_gaps": len(gaps),
        # diagnostics: a stall of the whole engine shows as one huge gap,
        # idle clients as few streams
        "itl_max_ms": 1e3 * max(gaps) if gaps else None,
        "streams_in_window": streaming,
    }
    if ttft:
        out["serve_ttft_p90_ms"] = 1e3 * trace_lib.percentile(ttft, 90)
        out["serve_ttft_p50_ms"] = 1e3 * trace_lib.percentile(ttft, 50)
    if gaps:
        median = trace_lib.percentile(gaps, 50)
        out["serve_itl_p95_ms"] = 1e3 * trace_lib.percentile(gaps, 95)
        out["serve_itl_p50_ms"] = 1e3 * median
        # a gap that holds an admission is a prefill call long, any
        # other a tick; a percentile reads one kind or the other by
        # which side of it the admission gaps' share lies (PERF.md, PR
        # 26): the 99th stands beside the 95th, and the share is printed
        # (and is every serving cell's serve_itl_long_gap_share_pct)
        out["serve_itl_p99_ms"] = 1e3 * trace_lib.percentile(gaps, 99)
        # the chat cell's percentile (README, "Which percentile": where
        # it stands between its two edges, and why it is this one)
        out["serve_itl_p995_ms"] = 1e3 * trace_lib.percentile(gaps, 99.5)
        out["itl_p90_ms"] = 1e3 * trace_lib.percentile(gaps, 90)
        out["itl_p999_ms"] = 1e3 * trace_lib.percentile(gaps, 99.9)
        # the two edges a held percentile stands between (README, "Which
        # percentile"): the gaps that hold an admission at all, and
        # those that hold a full-shape prefill call
        for factor in (3, 10):
            out[f"itl_over_{factor}x_median_share_pct"] = 100.0 * sum(
                g > factor * median for g in gaps) / len(gaps)
    return out


def judge_requests(records: List[Dict[str, Any]],
                   closed_loop: bool) -> Dict[str, Any]:
    """Every request that ended must be ``ok`` with the tokens asked.
    Closed loop: streams dropped when the window closed are not
    attempts. Open loop: every request sent is one."""
    attempted = failed = 0
    reasons: Dict[str, Any] = {}
    for r in records:
        if closed_loop and r["outcome"] == "dropped_at_stop":
            continue
        attempted += 1
        ok = (r["outcome"] == "ok" and r["tokens"] == r["max_new_tokens"])
        if not ok:
            failed += 1
            why = (r["outcome"] if r["outcome"] != "ok"
                   else "wrong_token_count")
            reasons[str(why)] = reasons.get(str(why), 0) + 1
            if r.get("error"):
                reasons.setdefault("first_error", f"{r.get('status')} "
                                   f"{str(r['error'])[-120:]}")
    return {"attempted": attempted, "failed": failed, "reasons": reasons}


def exhausted_clients(records: List[Dict[str, Any]],
                      per_client: int) -> List[int]:
    """Closed-loop clients that ended their last request before the
    window closed: from then on they offered no load."""
    ended: Dict[int, int] = {}
    for r in records:
        if r["outcome"] != "dropped_at_stop":
            ended[r["client"]] = ended.get(r["client"], 0) + 1
    return sorted(c for c, n in ended.items() if n >= per_client)


def memory_line(stage: str) -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    gib = {k: round(stats.get(k, 0) / 2**30, 2) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
        "peak_bytes_reserved", "bytes_limit")}
    return f"memory after {stage}: {gib} GiB"


def backlog_growth_s(records: List[Dict[str, Any]], t0: float,
                     t1: float) -> Optional[float]:
    """Mean TTFT of the window's last third of measured requests minus
    that of its first third: near zero when the rate is sustained,
    growing with the window when a backlog builds."""
    rows = sorted((r["due_t"], r["ttft_s"]) for r in records
                  if r["measured"] and t0 <= r["due_t"] < t1
                  and r.get("ttft_s") is not None)
    third = len(rows) // 3
    if third < 2:
        return None
    return float(np.mean([x[1] for x in rows[-third:]])
                 - np.mean([x[1] for x in rows[:third]]))


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import check as check_lib

    config, workload, traffic = ctx["config"], ctx["workload"], ctx["traffic"]
    log, tracer = ctx["log"], ctx["tracer"]
    check = workload.get("check", {})
    shape = config["serve"]
    vocab = int(config["vocab_size"])
    problems: List[str] = []
    reference = modules.reference_of(ctx["spec"], config)

    t = time.monotonic()
    cfg, init = program.serving_model(config, shape.get("dtype", "bfloat16"))
    params = jax.block_until_ready(make_params(init, cfg, ctx["seed"]))
    log(f"weights on device ({time.monotonic() - t:.1f}s)")

    t = time.monotonic()
    depth = int(check.get("decode_positions", 64))
    tokens, lens = traffic_lib.check_prompts(
        traffic, vocab, ctx["seed"], int(check.get("prompts", 8)), depth)
    ref = jax.block_until_ready(reference_logits(
        reference, config, check, params, tokens, lens, depth))
    log(f"reference logits {ref.shape} ({time.monotonic() - t:.1f}s)")
    tolerances = ({"rtol_of_max": float(check["rtol_of_max"])}
                  if "rtol_of_max" in check else {})
    wrong = {}
    for variant in workload.get("wrong_variants", []):
        off = reference_logits(reference, config, check, params, tokens,
                               lens, depth, wrong=variant)
        wrong[variant] = check_lib.judge_logits(
            float(jnp.max(jnp.abs(off - ref))), float(jnp.max(jnp.abs(ref))),
            **tolerances)
        del off
        log(f"wrong variant {variant}: {wrong[variant]}")
    log(memory_line("weights + reference"))

    t = time.monotonic()
    args, engine = build_engine(ctx, cfg, params)
    errors = system_logit_errors(engine, tokens, lens, depth, ref)
    del ref
    verdict = check_lib.judge_logits(
        errors["max_abs_err"], errors["max_abs_reference"], **tolerances)
    verdict.update(prompt_lens=[int(x) for x in lens],
                   prefill_max_abs_err=errors["prefill_max_abs_err"])
    if wrong:
        verdict["wrong_variants"] = wrong
    log(f"engine steps against the reference: {verdict} "
        f"({time.monotonic() - t:.1f}s)")
    log(memory_line("engine check"))
    if not (verdict["ok"] and errors["all_finite"]):
        problems.append("paged prefill/decode logits disagree with the "
                        "reference")
    want = workload.get("expect", {})
    if "decode_compile_count" in want and \
            engine.decode_compile_count != want["decode_compile_count"]:
        problems.append(f"decode compiled {engine.decode_compile_count} "
                        "times")

    if tracer.enabled:
        engine.tick = Annotated(engine.tick, "engine.tick", tracer)
        engine._prefill = Annotated(engine._prefill, "prefill.dispatch",
                                    tracer)
        engine._decode = Annotated(engine._decode, "decode.dispatch", tracer)
    records = Records()
    gateway = build_gateway(args, engine, records)
    gateway.start_in_thread()
    proc = None
    try:
        closed = traffic["kind"] == "closed_loop"
        # a traced run keeps the whole window (its record metrics need
        # the requests) and traces the first trace_seconds of it
        seconds = ctx["seconds"]
        lead_in = float(traffic["lead_in_s"])
        requests = traffic_lib.serve_requests(
            traffic, vocab, ctx["seed"], seconds)
        start_at = time.monotonic() + LOADGEN_START_S
        t0 = start_at + lead_in
        t1 = t0 + seconds
        give_up = float(workload.get("drain_limit_s", 60.0))
        job = {"host": args.serve_host, "port": gateway.port,
               "start_at": start_at, "stop_at": t1,
               "deadline": t1 + (2.0 if closed else give_up),
               "requests": requests}
        proc, pipe_thread, box = start_loadgen(job)
        setup_s = t0 - ctx["setup_start"]

        time.sleep(max(0.0, t0 - time.monotonic()))
        compiles_before = ctx["compiles"].snapshot()["backend_compiles"]
        engine_before = engine.metrics.snapshot()
        tracer.start()
        if tracer.enabled:
            time.sleep(max(0.0, min(t1, t0 + float(workload.get(
                "trace_seconds", seconds))) - time.monotonic()))
            tracer.stop()
        time.sleep(max(0.0, t1 - time.monotonic()))
        compiled = (ctx["compiles"].snapshot()["backend_compiles"]
                    - compiles_before)
        engine_after = engine.metrics.snapshot()
        pipe_thread.join(timeout=give_up + 30.0)
        if pipe_thread.is_alive() or "out" not in box:
            raise RuntimeError("the load generator did not end")
        client = json.loads(box["out"])
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        gateway.stop_sync(drain=True, timeout_s=30.0)

    sent = client["records"]
    if client["timed_out"] and not closed:
        problems.append("requests were still open at the drain limit")
    if compiled:
        problems.append(f"{compiled} programs compiled in the window")
    judged = judge_requests(sent, closed)
    if judged["failed"]:
        problems.append(f"{judged['failed']} of {judged['attempted']} "
                        f"requests did not end ok: {judged['reasons']}")
    if closed:
        idle = exhausted_clients(sent, int(traffic["requests_per_client"]))
        if idle:
            problems.append(f"clients {idle} ran out of requests before "
                            "the window closed")
    values = client_metrics(sent, t0, t1, give_up)
    values["backlog_growth_s"] = backlog_growth_s(sent, t0, t1)
    log(memory_line("window"))
    log(f"window {seconds:.1f}s: {judged['attempted']} requests ended, "
        f"{judged['failed']} failed; "
        + ", ".join(f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in values.items()))
    access = [dict(r, measured=True)
              for r in records.by_kind.get("access", [])
              if t0 <= r["emitted_t"] < t1 + give_up]
    counters = {
        "live_tokens_mean": live_tokens_mean(
            sent, tracer.started_at or t0, tracer.stopped_at or t1),
        "decode_steps": engine.metrics.decode_steps,
        "prefill_calls": engine.metrics.prefill_calls,
    }
    # the program's own counters: every number of the engine's snapshot,
    # as its change over the window
    for name, value in engine_after.items():
        if isinstance(value, (int, float)):
            counters[f"engine.{name}"] = value - engine_before.get(name, 0)
    return {
        "problems": problems,
        "attempted": judged["attempted"], "failed": judged["failed"],
        "setup_s": setup_s, "window_s": seconds,
        "values": values, "counters": counters,
        "records": {"access": access, "loadgen": sent},
        "check": verdict,
    }


def notes(ctx: Dict[str, Any], result: Dict[str, Any], peaks) -> List[str]:
    """Lines printed before the result: what the client saw beyond the
    cell's end-to-end metrics (TTFT, tokens/s: in no cell yet, PERF.md
    section 2), and where requests waited on their way to a slot."""
    waits = {}
    for field in ("queue_wait_s", "engine_queue_wait_s"):
        seen = [r[field] for r in result["records"]["access"]
                if r.get(field) is not None]
        if seen:
            waits[f"{field}_p95_ms"] = 1e3 * trace_lib.percentile(seen, 95)
    late = [r["late_s"] for r in result["records"]["loadgen"]
            if r.get("late_s") is not None]
    if late:
        waits["loadgen_late_p95_ms"] = 1e3 * trace_lib.percentile(late, 95)
    own = {k: v for k, v in result["counters"].items()
           if not k.startswith("engine.")}
    return [f"client view: {json.dumps(result['values'])}",
            f"waits: {json.dumps(waits)}",
            f"engine: {own}"]

"""The engine's phase clocks and the spans that share their boundaries.

Quick tier, CPU. Every instant of the engine thread is booked to one of
three clocks (stall / device wait / host); a request's share of each
between its first token and its retirement rides its terminal result;
a tick over ``SLOW_TICK_S`` is reported with its phases; and the same
boundaries are ``engine.tick.*`` spans in any profiler trace, with no
tracer and no telemetry directory configured.
"""

import contextlib
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
)
from scaletorch_tpu.inference import engine as engine_module
from scaletorch_tpu.models import llama

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)

TICK_PHASES = (
    "engine.tick.sweep", "engine.tick.admit", "engine.tick.prefill",
    "engine.tick.prefill_wait", "engine.tick.feed", "engine.tick.decode",
    "engine.tick.decode_wait", "engine.tick.emit", "engine.tick.export",
)
LOOP_PHASES = (
    "engine.tick_loop.inbox", "engine.tick_loop.deliver",
    "engine.tick_loop.idle",
)


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("sampling", SamplingParams(temperature=0.0))
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


class Collected:
    """An exporter that keeps its records."""

    def __init__(self):
        self.records = []

    def emit(self, kind, record):
        self.records.append((kind, record))


class DeviceClock:
    """``time.monotonic`` for the engine's thread, injected: a
    microsecond a reading, and a millisecond more whenever a phase that
    waits for the device ends (``waited``, in ``span``'s place). Host
    phases take microseconds and device waits milliseconds, as on a
    chip, whatever else the machine is doing. It starts at the real
    clock's reading: the engine's cumulative clocks were opened on that
    one, and a jump at the hand-over (to 5000.0, until PR 60) went into
    them as a term of the host's uptime, whose rounding (1e-9 once
    ``time.monotonic()`` passed 2**18 s) the partition then carried."""

    def __init__(self):
        self.t = time.monotonic()

    def __call__(self):
        self.t += 1e-6
        return self.t

    def waited(self, real_span):
        @contextlib.contextmanager
        def span(name, *args, **kw):
            with real_span(name, *args, **kw) as opened:
                yield opened
                if name.endswith("_wait"):
                    self.t += 1e-3
        return span


def test_the_three_clocks_sum_to_the_decode_life(tiny_llama, monkeypatch):
    """Two overlapping requests, the second admitted while the first
    decodes: for each, stall + device wait + host is its first token to
    its last, and the first stood still for the second's admission. On
    an injected clock: the partition is arithmetic on the engine's own
    readings, not a comparison of two wall clocks (ROADMAP D24)."""
    emitted = {}

    def on_tokens(slot, request_id, tokens, emitted_t):
        emitted.setdefault(request_id, []).append(emitted_t)

    eng = make_engine(tiny_llama, on_tokens=on_tokens)
    # both steps compiled before anything is timed: a token reaches the
    # hook once the next step is dispatched, and a first dispatch compiles
    eng.submit([9, 9], max_new_tokens=3)
    eng.run()
    clock = DeviceClock()
    monkeypatch.setattr(engine_module.time, "monotonic", clock)
    monkeypatch.setattr(engine_module, "span",
                        clock.waited(engine_module.span))
    first = eng.submit([1, 2, 3], max_new_tokens=12)
    for _ in range(4):
        eng.step()
    second = eng.submit([4, 5, 6, 7], max_new_tokens=6)
    results = eng.run()
    for rid in (first, second):
        r = results[rid]
        assert r.outcome == "ok"
        life = r.latency_s - r.ttft_s       # first token -> last token
        assert r.stall_s + r.device_wait_s + r.host_s == pytest.approx(
            life, abs=1e-9)
        assert min(r.stall_s, r.device_wait_s, r.host_s) >= 0
        # a wait for the device every decode step of its life
        assert r.device_wait_s >= 1e-3 * (len(r.tokens) - 1)
        # the engine's token times are the ones the stream is handed:
        # the reading a step's tokens are stamped with is the reading
        # its slots' clocks are closed at
        seen = emitted[rid]
        assert len(seen) == len(r.tokens) and seen == sorted(seen)
        assert life == pytest.approx(seen[-1] - seen[0], abs=1e-9)
    # the first stream stood still while the second was admitted (the
    # admission built, its call dispatched and waited for: what the
    # host spent beside it on the two decode steps is not stall); the
    # second's own admission came before its first token
    assert results[first].stall_s >= 1e-3
    assert results[second].prefill_s >= 1e-3
    assert results[second].stall_s < 1e-3 <= results[second].prefill_s


def test_a_tick_that_admits_is_partitioned_and_times_its_call(
        tiny_llama, monkeypatch):
    """A step in flight and an admission due: the tick's phases come in
    the order docs/observability.md gives (everything dispatched, then
    everything read), every instant of it is booked to one clock, the
    wait for the call is stall and the wait for the step in flight
    device wait, and ``prefill_s`` runs from the call's dispatch to its
    readback: the next step's dispatch and what was left of the step in
    flight are inside it."""
    eng = make_engine(tiny_llama)
    eng.submit([9, 9], max_new_tokens=3)
    eng.run()                                 # both steps compiled
    eng.submit([1, 2, 3], max_new_tokens=24)
    for _ in range(3):
        eng.step()
    assert eng._in_flight is not None
    rid = eng.submit([4, 5, 6, 7], max_new_tokens=2)
    names, phases = [], {}
    real_span, close = engine_module.span, eng._close_tick

    def recording_span(name, *args, **kw):
        names.append(name)
        return real_span(name, *args, **kw)

    def closing(*args):
        phases.update(eng._tick_phase_s)
        close(*args)

    monkeypatch.setattr(engine_module, "span", recording_span)
    eng._close_tick = closing
    t0 = time.monotonic()
    eng._advance(t0)
    before = list(eng._clocks)
    eng.step()
    t1 = time.monotonic()
    eng._advance(t1)
    eng._close_tick = close
    assert names == [
        "engine.tick", "engine.tick.sweep", "engine.tick.admit",
        "engine.tick.prefill",                          # the call, behind n
        "engine.tick.feed", "engine.tick.decode",       # n+1, behind it
        "engine.tick.decode_wait",       # on_dispatched (the results)
        # n read; its tokens handed over inside the emit
        "engine.tick.decode_wait", "engine.tick.emit",
        # the call read; its first tokens handed over inside the emit
        "engine.tick.prefill_wait", "engine.tick.emit",
        "engine.tick.export"]
    assert eng.metrics.prefill_calls_behind_flight == 1
    spent = [c - c0 for c, c0 in zip(eng._clocks, before)]
    assert sum(spent) == pytest.approx(t1 - t0, abs=1e-9)
    stall = sum(phases[f"engine.tick.{name}"] for name in (
        "sweep", "admit", "prefill", "prefill_wait"))
    assert spent[engine_module.STALL] == pytest.approx(stall, abs=1e-9)
    assert spent[engine_module.DEVICE_WAIT] == pytest.approx(
        phases["engine.tick.decode_wait"], abs=1e-9)
    prefill_s = eng.run()[rid].prefill_s
    inside = sum(phases[f"engine.tick.{name}"] for name in (
        "prefill", "feed", "decode", "decode_wait", "prefill_wait"))
    assert inside <= prefill_s <= inside + phases["engine.tick.emit"] + 1e-3


def test_the_clocks_partition_the_wall_time_with_a_step_in_flight(
        tiny_llama, monkeypatch):
    """The loop one step ahead: every instant of the ticking thread is
    still booked to one clock, the wait for the step in flight is
    device wait, and a plain tick's phases come in the order
    docs/observability.md gives: the dispatch of the step ahead, what
    runs beside it (``on_dispatched``), then the read of the step
    before it, whose tokens are handed over inside its emit."""
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=24)
    eng.step()
    eng.step()                                # compiled, a step in flight
    names = []
    real_span = engine_module.span

    def recording_span(name, *args, **kw):
        names.append(name)
        return real_span(name, *args, **kw)

    monkeypatch.setattr(engine_module, "span", recording_span)
    t0 = time.monotonic()
    eng._advance(t0)
    before = list(eng._clocks)
    for _ in range(6):
        assert eng._in_flight is not None
        eng.step()
    t1 = time.monotonic()
    eng._advance(t1)
    spent = [c - c0 for c, c0 in zip(eng._clocks, before)]
    assert sum(spent) == pytest.approx(t1 - t0, abs=1e-9)
    assert min(spent) >= 0 and spent[engine_module.DEVICE_WAIT] > 0
    assert spent[engine_module.STALL] < spent[engine_module.HOST]
    tick = ["engine.tick", "engine.tick.sweep", "engine.tick.admit",
            "engine.tick.feed", "engine.tick.decode",
            "engine.tick.decode_wait", "engine.tick.decode_wait",
            "engine.tick.emit", "engine.tick.export"]
    assert names == tick * 6


def test_a_request_that_never_reached_a_slot_has_no_clocks(tiny_llama):
    eng = make_engine(tiny_llama, strict_submit=False)
    rid = eng.submit([], max_new_tokens=4)
    r = eng.result(rid)
    assert r.outcome == "rejected"
    assert (r.stall_s, r.device_wait_s, r.host_s) == (None, None, None)


def test_an_aborted_stream_is_booked_up_to_its_retirement(tiny_llama):
    eng = make_engine(tiny_llama)
    rid = eng.submit([1, 2, 3], max_new_tokens=20)
    for _ in range(3):
        eng.step()
    time.sleep(0.05)
    assert eng.cancel(rid)
    r = eng.result(rid)
    assert r.outcome == "aborted" and len(r.tokens) >= 2
    assert r.stall_s + r.device_wait_s + r.host_s == pytest.approx(
        r.latency_s - r.ttft_s, abs=1e-9)
    # the 50 ms between the last tick and the cancel are host time
    assert r.host_s >= 0.05


def test_slow_tick_reports_its_phases_once(tiny_llama, monkeypatch):
    """The injector's slow decode stalls one tick past the constant:
    one ``slow_tick`` record with the stall under ``decode_wait``.
    An ordinary prefill tick, and a tick after an idle pause, are not
    slow ticks."""
    records = Collected()
    eng = make_engine(
        tiny_llama, exporter=records,
        monitor_every=10_000,
        injector=ServingFaultInjector(
            slow_decode_at_step=9, slow_decode_seconds=0.4))
    eng.submit([1, 2, 3], max_new_tokens=4)   # compiles both steps
    eng.run()
    assert eng.metrics.decode_steps < 9
    monkeypatch.setattr(engine_module, "SLOW_TICK_S", 0.2)
    warm = eng.metrics.slow_ticks
    seen = len(records.records)

    time.sleep(0.3)                           # idle: nothing was pending
    eng.submit([4, 5, 6, 7], max_new_tokens=3)
    eng.run()                                 # a prefill tick + decodes
    assert eng.metrics.decode_steps < 9
    assert eng.metrics.slow_ticks == warm

    eng.submit([1, 2, 3, 4], max_new_tokens=8)
    eng.run()                                 # decode step 9 stalls
    assert eng.metrics.decode_steps > 9
    assert eng.metrics.slow_ticks == warm + 1
    assert eng.metrics.snapshot()["slow_ticks"] == warm + 1
    slow = [r for kind, r in records.records[seen:] if kind == "slow_tick"]
    assert len(slow) == 1
    record = slow[0]
    assert record["tick"] == 9
    assert record["phases_s"]["engine.tick.decode_wait"] >= 0.4
    assert record["wall_s"] >= 0.4
    assert record["gap_before_s"] < 0.2
    assert set(record["phases_s"]) <= set(TICK_PHASES)


def test_the_gap_before_a_tick_counts_when_work_was_waiting(
        tiny_llama, monkeypatch):
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=16)
    for _ in range(4):
        eng.step()                            # compiled, still decoding
    monkeypatch.setattr(engine_module, "SLOW_TICK_S", 0.2)
    warm = eng.metrics.slow_ticks
    time.sleep(0.3)                           # the driver of step() stalls
    eng.step()
    assert eng.metrics.slow_ticks == warm + 1
    eng.step()
    assert eng.metrics.slow_ticks == warm + 1


def load_host_events(log_dir):
    from jax.profiler import ProfileData

    [path] = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                if event.name.startswith("engine."):
                    events.append({
                        "plane": plane.name, "line": line.name,
                        "name": event.name, "start": event.start_ns,
                        "end": event.start_ns + event.duration_ns,
                        "stats": dict(event.stats)})
    return events


def test_a_profiler_trace_holds_every_phase_with_nothing_configured(
        tiny_llama, tmp_path):
    """No tracer, no telemetry directory: a ``jax.profiler`` session
    around a few ticks of the worker loop still shows the whole
    vocabulary on a host plane, the tick's phases inside
    ``engine.tick``."""
    from scaletorch_tpu.serving.gateway import EngineWorker
    from scaletorch_tpu.serving.protocol import GenerateRequest

    eng = make_engine(tiny_llama)
    assert eng.tracer is None and eng.exporter is None
    eng.submit([1, 2, 3], max_new_tokens=2)
    eng.run()                                 # compile outside the trace
    worker = EngineWorker(eng, replica_id="r0", idle_wait_s=0.01)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        worker.start()
        done = threading.Event()
        worker.submit(GenerateRequest(prompt=[5, 6, 7], max_new_tokens=4),
                      lambda rid, toks, _t: None, lambda result: done.set())
        assert done.wait(timeout=60)
        time.sleep(0.05)                      # a few idle waits
        worker.shutdown(drain=True)
        worker.join(timeout=60)
        assert not worker.alive
    finally:
        jax.profiler.stop_trace()

    events = load_host_events(str(tmp_path))
    assert events and not any(
        e["plane"].startswith("/device:") for e in events)
    names = {e["name"] for e in events}
    assert set(TICK_PHASES) | set(LOOP_PHASES) | {"engine.tick"} <= names
    ticks = [e for e in events if e["name"] == "engine.tick"]
    # the outer span alone carries an argument: the tick number
    assert all("tick" in e["stats"] for e in ticks)
    for e in events:
        if e["name"] in TICK_PHASES:
            assert not e["stats"]
            assert any(t["line"] == e["line"] and t["start"] <= e["start"]
                       and e["end"] <= t["end"] for t in ticks), e
        elif e["name"] in LOOP_PHASES:
            assert not any(t["line"] == e["line"] and t["start"] < e["end"]
                           and e["start"] < t["end"] for t in ticks), e

"""Unified telemetry (scaletorch_tpu/telemetry/): spans, profiling,
stragglers, export — unit + hermetic end-to-end.

The e2e layer reuses the test_resilience ``ToyTrainer`` discipline: the
REAL ``Trainer.train`` loop (telemetry hooks and all) over a tiny
mesh-free step, so the instrumentation under test is the production
instrumentation. Acceptance surface (ISSUE 9):

  * the Chrome-trace JSON loads (valid trace-event schema) and contains
    data_fetch / step_dispatch / checkpoint_save spans;
  * the JSONL stream is schema-valid with one record per logged step;
  * an injected slow step (--ft_slow_step_at_step) arms EXACTLY ONE
    bounded profiler window under --telemetry_dir;
  * a threaded 4-host FakeBus run with one delayed host surfaces that
    host's index in the straggler report;
  * with telemetry disabled, the instrumented loop's per-step overhead
    is within noise of a no-telemetry run (asserted loosely).

ISSUE 24: ``telemetry.span`` is the one span API, always a profiler
annotation and a Chrome event too under a tracer; the overhead contract
is the measured cost of a tick's whole span-and-clock set.
"""

import gc
import glob
import json
import logging
import os
import signal
import threading
import time

import numpy as np
import pytest

from scaletorch_tpu.telemetry import (
    SCHEMA_VERSION,
    AnomalyProfiler,
    LiveSnapshotter,
    SlowStepDetector,
    SpanTracer,
    StragglerDetector,
    Telemetry,
    TelemetryExporter,
    collection_counters,
    load_trace,
    observe_collections,
    parse_profile_steps,
    span,
)
from scaletorch_tpu.telemetry.export import read_jsonl, render_prometheus
from scaletorch_tpu.trainer.trainer import Trainer
from tests.test_resilience import ToyTrainer, e2e_cfg, e2e_tokens


def host_span_names(log_dir):
    """Names of the events on the non-device planes of the newest
    profiler capture under ``log_dir``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
    return {event.name
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for event in line.events}


# ---------------------------------------------------------------------------
# SpanTracer
# ---------------------------------------------------------------------------


class TestSpanTracer:
    def test_trace_file_is_valid_chrome_trace(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        tr = SpanTracer(path, process_index=3)
        with tr.span("data_fetch", step=1):
            pass
        tr.counter("straggler_flags", 2)
        tr.close()
        events = json.load(open(path))  # valid JSON after close()
        assert isinstance(events, list)
        by_name = {e["name"]: e for e in events}
        span = by_name["data_fetch"]
        # trace-event schema: complete events need ph/ts/dur/pid/tid
        assert span["ph"] == "X" and span["dur"] >= 0
        assert span["pid"] == 3 and "tid" in span and "ts" in span
        assert span["args"] == {"step": 1}
        assert by_name["straggler_flags"]["ph"] == "C"
        assert by_name["straggler_flags"]["args"]["value"] == 2
        assert by_name["process_name"]["ph"] == "M"

    def test_phase_track_closes_previous_and_survives_crash(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        tr = SpanTracer(path)
        tr.phase("step_boundary", step=0)
        tr.phase("data_fetch", step=0)
        tr.phase("step_dispatch", step=0)
        tr.flush()
        # no close(): the unterminated file must still load (the
        # crashed-run form Perfetto tolerates)
        events = load_trace(path)
        names = [e["name"] for e in events if e.get("ph") == "X"]
        assert names == ["step_boundary", "data_fetch"]  # dispatch open
        tr.close()
        names = [e["name"] for e in json.load(open(path))
                 if e.get("ph") == "X"]
        assert names == ["step_boundary", "data_fetch", "step_dispatch"]

    def test_tail_keeps_newest_and_is_capped(self, tmp_path):
        tr = SpanTracer(str(tmp_path / "t.trace.json"), tail_size=4)
        for i in range(10):
            with tr.span(f"s{i}"):
                pass
        tail = tr.tail()
        assert [e["name"] for e in tail] == ["s6", "s7", "s8", "s9"]
        assert [e["name"] for e in tr.tail(2)] == ["s8", "s9"]
        tr.close()

    def test_max_events_caps_file_but_not_tail(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        tr = SpanTracer(path, max_events=3, tail_size=16)
        for i in range(6):
            with tr.span(f"s{i}"):
                pass
        tr.close()
        assert tr.events_dropped == 3
        events = json.load(open(path))
        file_names = [e["name"] for e in events if e.get("ph") == "X"]
        assert file_names == ["s0", "s1", "s2"]
        # the drop count is recorded in metadata so a reader knows the
        # timeline is incomplete
        [drop] = [e for e in events if e["name"] == "events_dropped"]
        assert drop["args"]["count"] == 3
        # the tail keeps the NEWEST — crash reports want the end
        assert [e["name"] for e in tr.tail(3)] == ["s3", "s4", "s5"]

    def test_lock_reentrant_from_signal_handler_context(self):
        # A SIGUSR1 live-snapshot handler runs on the main thread and
        # reads tail() — which must not deadlock when the signal landed
        # while that same thread held the lock inside _emit.
        tr = SpanTracer(path=None)
        tr.counter("x", 1)
        with tr._lock:  # simulate: handler fires mid-_emit
            assert tr._lock.acquire(blocking=False), (
                "tracer lock must be reentrant (SIGUSR1 handler reads "
                "tail() on the thread that may hold it)")
            tr._lock.release()
            assert tr.tail()[-1]["name"] == "x"

    def test_memory_only_tracer_writes_no_file(self, tmp_path):
        tr = SpanTracer(None)
        with tr.span("x"):
            pass
        assert len(tr.tail()) == 1
        tr.close()
        assert list(tmp_path.iterdir()) == []

    def test_disabled_tracer_records_nothing(self):
        tr = SpanTracer(None, enabled=False)
        with tr.span("x"):
            pass
        tr.phase("a")
        tr.counter("c", 1)
        assert tr.tail() == []

    def test_close_is_idempotent_and_disables(self, tmp_path):
        path = str(tmp_path / "t.trace.json")
        tr = SpanTracer(path)
        with tr.span("x"):
            pass
        tr.close()
        tr.close()
        with tr.span("y"):
            pass
        assert [e["name"] for e in json.load(open(path))
                if e.get("ph") == "X"] == ["x"]

    @pytest.mark.parametrize("sink", ["none", "disabled", "tracer"])
    def test_one_span_two_sinks(self, tmp_path, sink):
        """``span`` is a profiler annotation whatever is attached, and
        a Chrome event too under an enabled tracer; so is a phase."""
        import jax

        tr = {"none": None, "disabled": SpanTracer(None, enabled=False),
              "tracer": SpanTracer(None)}[sink]
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("unit.outer", tr, step=7):
                with span("unit.inner", tr):
                    pass
            if tr is not None:
                tr.phase("unit.phase")
                tr.end_phase()
        finally:
            jax.profiler.stop_trace()
        names = host_span_names(str(tmp_path))
        assert {"unit.outer", "unit.inner"} <= names
        assert ("unit.phase" in names) == (sink == "tracer")
        chrome = [] if tr is None else tr.tail()
        if sink == "tracer":
            assert [e["name"] for e in chrome] == [
                "unit.inner", "unit.outer", "unit.phase"]
            assert chrome[1]["args"] == {"step": 7}
        else:
            assert chrome == []


# ---------------------------------------------------------------------------
# Export: JSONL + Prometheus
# ---------------------------------------------------------------------------


class TestExport:
    def test_jsonl_schema_envelope(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        ex = TelemetryExporter(path, process_index=2)
        ex.emit("train_step", {"step": 1, "loss": 2.5})
        ex.emit("engine_metrics", {"tokens_per_second": 10.0})
        ex.close()
        lines = read_jsonl(path)
        assert len(lines) == 2
        for line in lines:
            assert line["v"] == SCHEMA_VERSION
            assert line["proc"] == 2
            assert line["time"] > 0
        assert lines[0]["kind"] == "train_step" and lines[0]["step"] == 1
        assert lines[1]["kind"] == "engine_metrics"

    def test_non_serialisable_values_reprd_not_dropped(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        ex = TelemetryExporter(path)
        ex.emit("train_step", {"weird": object()})
        ex.close()
        assert "object object" in read_jsonl(path)[0]["weird"]

    def test_render_prometheus_text_format(self):
        body = render_prometheus(
            {"tokens/s": 5.0, "occupancy": 0.5, "label": "skip-me"})
        assert "# TYPE scaletorch_occupancy gauge" in body
        assert "scaletorch_occupancy 0.5" in body
        assert "scaletorch_tokens_s 5.0" in body  # name sanitised
        assert "skip-me" not in body              # non-numeric skipped
        assert body.endswith("\n")


# ---------------------------------------------------------------------------
# The interpreter's collections (telemetry/spans.py, CollectionObserver)
# ---------------------------------------------------------------------------


class StubAnnotation:
    """In ``_annotation``'s place: what was opened and closed."""

    def __init__(self, log, name, args):
        self._log, self._name = log, name
        assert not args

    def __enter__(self):
        self._log.append(("enter", self._name))
        return self

    def __exit__(self, *exc):
        self._log.append(("exit", self._name))


@pytest.fixture
def collections(monkeypatch):
    """The process's observer with the collector held still around it
    (only a forced collection runs), a memory-only tracer on its list
    and a stub in the profiler annotation's place."""
    from scaletorch_tpu.telemetry import spans as spans_mod

    log = []
    monkeypatch.setattr(
        spans_mod, "_annotation",
        lambda name, args: StubAnnotation(log, name, args))
    tracer = SpanTracer()
    observer = observe_collections(tracer)
    gc.collect()  # what the set-up left behind is not the test's
    log.clear()
    gc.disable()
    try:
        yield observer, tracer, log
    finally:
        gc.enable()
        tracer.close()


def full_events(tracer):
    return [e for e in tracer.tail() if e["name"] == "host.gc.full"]


class TestCollectionObserver:
    def test_installing_twice_registers_one_callback(self):
        tracer = SpanTracer()
        first = observe_collections(tracer)
        assert observe_collections(tracer) is first
        assert observe_collections() is first
        assert gc.callbacks.count(first) == 1
        assert first.tracers.count(tracer) == 1
        # a closed tracer leaves the list at the next call
        tracer.close()
        observe_collections()
        assert tracer not in first.tracers

    def test_a_forced_full_collection_is_counted_timed_and_spanned(
            self, collections):
        observer, tracer, log = collections
        before, already = collection_counters(), len(full_events(tracer))
        t0 = time.monotonic()
        gc.collect()
        wall = time.monotonic() - t0
        after = collection_counters()
        assert after["host_gc_full_collections"] \
            == before["host_gc_full_collections"] + 1
        assert after["host_gc_collections"] \
            == before["host_gc_collections"] + 1
        pause = after["host_gc_full_pause_s"] - before["host_gc_full_pause_s"]
        assert 0 < pause <= wall
        # all generations' pause holds the full one's
        assert after["host_gc_pause_s"] - before["host_gc_pause_s"] \
            == pytest.approx(pause, abs=1e-12)
        # exactly one span, entered at start and left at stop, in the
        # profiler's sink and in the tracer's
        assert log == [("enter", "host.gc.full"), ("exit", "host.gc.full")]
        (event,) = full_events(tracer)[already:]
        assert event["ph"] == "X" and event["cat"] == "host"
        assert event["dur"] == int(pause * 1e6)
        assert set(event["args"]) == {"collected"}
        assert event["tid"] == threading.get_native_id()

    @pytest.mark.parametrize("generation", [0, 1])
    def test_a_young_collection_is_summed_and_opens_no_span(
            self, collections, generation):
        observer, tracer, log = collections
        before, already = collection_counters(), len(full_events(tracer))
        gc.collect(generation)
        after = collection_counters()
        assert after["host_gc_collections"] \
            == before["host_gc_collections"] + 1
        assert after["host_gc_pause_s"] > before["host_gc_pause_s"]
        assert after["host_gc_full_collections"] \
            == before["host_gc_full_collections"]
        assert after["host_gc_full_pause_s"] == before["host_gc_full_pause_s"]
        assert log == [] and len(full_events(tracer)) == already

    def test_a_disabled_tracer_gets_no_event(self, collections):
        observer, tracer, log = collections
        off = SpanTracer(enabled=False)
        observe_collections(off)
        assert off not in observer.tracers
        gc.collect()
        assert off.tail() == [] and len(full_events(tracer)) >= 1

    def test_a_young_collections_callbacks_cost_a_few_microseconds_at_most(
            self):
        """A compare, two clock reads and two adds: the best of seven
        rounds against a ceiling in absolute time (0.4 us on an idle
        core; 1,000 a second is then 0.04 % of a thread)."""
        import timeit

        observer = observe_collections()
        info = {"generation": 0, "collected": 0, "uncollectable": 0}

        def one_collection():
            observer("start", info)
            observer("stop", info)

        was = gc.isenabled()
        gc.disable()
        try:
            before = collection_counters()
            per = min(timeit.repeat(
                one_collection, number=5_000, repeat=7)) / 5_000
            after = collection_counters()
        finally:
            if was:
                gc.enable()
        assert per < 5e-6, f"{per * 1e6:.2f} us a young collection"
        assert after["host_gc_collections"] \
            == before["host_gc_collections"] + 35_000
        assert after["host_gc_full_collections"] \
            == before["host_gc_full_collections"]

    def test_a_tracers_event_is_one_write_with_its_separator(self, tmp_path):
        """A full collection tripped inside ``_emit`` sends its own
        event through ``_emit`` on the same thread: each event reaches
        the file in one write, so the array stays an array."""
        path = str(tmp_path / "t.trace.json")
        tracer = SpanTracer(path)
        writes = []

        class Recording:
            def __init__(self, real):
                self._real = real

            def write(self, text):
                writes.append(text)
                return self._real.write(text)

            def __getattr__(self, name):
                return getattr(self._real, name)

        tracer.counter("a", 1)
        tracer._file = Recording(tracer._file)
        tracer.counter("b", 2)
        tracer.counter("c", 3)
        assert len(writes) == 2 and all(w.startswith(",\n{") for w in writes)
        tracer.close()
        assert [e["name"] for e in load_trace(path)
                if e["ph"] == "C"] == ["a", "b", "c"]

    def test_the_engine_installs_it_and_its_snapshot_carries_the_four(self):
        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.inference import InferenceEngine, SamplingParams
        from scaletorch_tpu.inference.engine import EngineMetrics
        from scaletorch_tpu.models import llama

        names = {"host_gc_collections", "host_gc_pause_s",
                 "host_gc_full_collections", "host_gc_full_pause_s"}
        assert names <= set(EngineMetrics().snapshot())
        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, dtype=jnp.float32,
        )
        tracer = SpanTracer()
        eng = InferenceEngine(
            llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
            max_slots=2, max_seq=16, prefill_len=8, page_size=4,
            sampling=SamplingParams(temperature=0.0), tracer=tracer)
        observer = observe_collections()
        assert gc.callbacks.count(observer) == 1
        assert tracer in observer.tracers
        before = eng.metrics.snapshot()
        gc.collect()
        after = eng.metrics.snapshot()
        assert after["host_gc_full_collections"] \
            >= before["host_gc_full_collections"] + 1
        assert after["host_gc_full_pause_s"] > before["host_gc_full_pause_s"]
        assert all(isinstance(after[name], (int, float)) for name in names)
        assert full_events(tracer)
        tracer.close()


# ---------------------------------------------------------------------------
# Slow-step detector + anomaly profiler (fake backend)
# ---------------------------------------------------------------------------


class FakeBackend:
    def __init__(self, fail_start=False):
        self.calls = []
        self.fail_start = fail_start

    def start(self, log_dir):
        if self.fail_start:
            raise RuntimeError("no profiler here")
        self.calls.append(("start", log_dir))

    def stop(self):
        self.calls.append(("stop", None))


class TestSlowStepDetector:
    def test_warmup_discarded_entirely(self):
        d = SlowStepDetector(3.0, warmup_steps=2)
        assert not d.observe(10.0)    # cold compile: discarded
        assert not d.observe(100.0)   # still warmup: discarded
        assert d.ema is None          # the compile never seeds the EMA
        assert not d.observe(1.0)     # seeds the baseline
        assert d.ema == 1.0 and d.spikes == 0

    def test_spike_detected_and_never_feeds_ema(self):
        d = SlowStepDetector(2.0, ema_beta=0.5, warmup_steps=1)
        d.observe(99.0)              # discarded (compile)
        d.observe(1.0)               # seeds the EMA
        assert d.observe(10.0)       # 10 > 2 * 1.0
        assert d.ema == 1.0          # anomaly excluded from the baseline
        assert not d.observe(1.2)
        assert d.ema == pytest.approx(1.1)

    def test_validation(self):
        with pytest.raises(ValueError, match="spike_factor"):
            SlowStepDetector(1.0)
        with pytest.raises(ValueError, match="ema_beta"):
            SlowStepDetector(2.0, ema_beta=1.0)


class TestAnomalyProfiler:
    def test_slow_step_arms_exactly_one_bounded_window(self, tmp_path):
        be = FakeBackend()
        p = AnomalyProfiler(str(tmp_path), window_steps=2,
                            spike_factor=3.0, max_captures=1, backend=be)
        times = [0.01, 0.01, 0.01, 0.5, 0.01, 0.01, 0.5, 0.01, 0.01]
        for step, t in enumerate(times, start=1):
            p.before_step(step)
            p.after_step(step, t)
        p.close()
        # one window despite TWO slow steps: max_captures bounds it
        assert len(p.captures) == 1
        cap = p.captures[0]
        assert cap["trigger"] == "slow_step"
        assert (cap["start_step"], cap["stop_step"]) == (5, 7)  # bounded
        assert be.calls == [
            ("start", cap["dir"]), ("stop", None)]

    def test_manual_window_covers_start_to_stop(self, tmp_path):
        be = FakeBackend()
        p = AnomalyProfiler(str(tmp_path), profile_steps=(3, 5), backend=be)
        for step in range(1, 8):
            p.before_step(step)
            p.after_step(step, 0.01)
        p.close()
        assert len(p.captures) == 1
        assert p.captures[0]["trigger"] == "manual"
        assert (p.captures[0]["start_step"],
                p.captures[0]["stop_step"]) == (3, 5)

    def test_manual_window_opens_late_on_resumed_run(self, tmp_path):
        # --resume past the start step: the remainder of the window is
        # still captured (>= not ==)
        be = FakeBackend()
        p = AnomalyProfiler(str(tmp_path), profile_steps=(3, 6), backend=be)
        for step in range(5, 9):
            p.before_step(step)
            p.after_step(step, 0.01)
        p.close()
        assert len(p.captures) == 1
        assert (p.captures[0]["start_step"],
                p.captures[0]["stop_step"]) == (5, 6)

    def test_manual_window_entirely_past_is_spent_not_retried(self, tmp_path):
        be = FakeBackend()
        p = AnomalyProfiler(str(tmp_path), profile_steps=(3, 6), backend=be)
        p.before_step(10)  # resumed beyond the whole window: warns once
        assert p._manual_done
        p.after_step(10, 0.01)
        p.close()
        assert p.captures == [] and be.calls == []

    def test_run_end_mid_window_still_stops(self, tmp_path):
        be = FakeBackend()
        p = AnomalyProfiler(str(tmp_path), profile_steps=(2, 100), backend=be)
        p.before_step(1)
        p.after_step(1, 0.01)
        p.before_step(2)
        assert p.active
        p.close()
        assert not p.active
        assert be.calls[-1] == ("stop", None)
        assert len(p.captures) == 1

    def test_broken_backend_degrades_and_stops_rearming(self, tmp_path):
        p = AnomalyProfiler(str(tmp_path), window_steps=1, spike_factor=2.0,
                            max_captures=5, backend=FakeBackend(True))
        for step, t in enumerate([0.01, 0.01, 0.01, 1.0, 0.01, 1.0], 1):
            p.before_step(step)
            p.after_step(step, t)
        assert p.captures == [] and p._broken

    def test_parse_profile_steps(self):
        assert parse_profile_steps("") is None
        assert parse_profile_steps("3:7") == (3, 7)
        for bad in ("7:3", "0:4", "x:y", "3", "3:4:5"):
            with pytest.raises(ValueError):
                parse_profile_steps(bad)


# ---------------------------------------------------------------------------
# SIGUSR1 live snapshot
# ---------------------------------------------------------------------------


class TestLiveSnapshotter:
    def test_sigusr1_dumps_without_stopping(self, tmp_path):
        snap = LiveSnapshotter(
            str(tmp_path), lambda: {"step": 7, "span_tail": [{"name": "x"}]})
        with snap:
            os.kill(os.getpid(), signal.SIGUSR1)
            # the handler runs between bytecodes; this loop keeps running
            deadline = time.monotonic() + 5
            while snap.snapshots_written == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
        assert snap.snapshots_written == 1
        payload = json.load(open(tmp_path / "live_snapshot_1.json"))
        assert payload["step"] == 7
        assert payload["span_tail"] == [{"name": "x"}]
        assert "MainThread" in payload["thread_stacks"]

    def test_broken_snapshot_fn_never_kills_the_run(self, tmp_path):
        def broken():
            raise RuntimeError("boom")

        snap = LiveSnapshotter(str(tmp_path), broken)
        with snap:
            os.kill(os.getpid(), signal.SIGUSR1)
            deadline = time.monotonic() + 5
            while snap.snapshots_written == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
        payload = json.load(open(tmp_path / "live_snapshot_1.json"))
        assert "boom" in payload["snapshot_error"]

    def test_uninstall_restores_previous_handler(self, tmp_path):
        prev = signal.getsignal(signal.SIGUSR1)
        snap = LiveSnapshotter(str(tmp_path), dict)
        snap.install()
        snap.uninstall()
        assert signal.getsignal(signal.SIGUSR1) == prev


# ---------------------------------------------------------------------------
# Straggler detector (unit)
# ---------------------------------------------------------------------------


class TestStragglerDetector:
    def test_summary_names_argmax_host(self):
        d = StragglerDetector(factor=2.0, patience=3)
        s = d.observe(1, [{"step_time": 0.1, "data_fetch_time": 0.01},
                          {"step_time": 0.3, "data_fetch_time": 0.2},
                          {"step_time": 0.1, "data_fetch_time": 0.01}])
        assert s["step_time_argmax_host"] == 1
        assert s["step_time_max"] == pytest.approx(0.3)
        assert s["step_time_p50"] == pytest.approx(0.1)
        assert s["data_fetch_argmax_host"] == 1

    def test_persistence_needs_patience(self):
        d = StragglerDetector(factor=2.0, patience=3)
        obs = [{"step_time": 0.1}, {"step_time": 0.1}, {"step_time": 0.5}]
        d.observe(1, obs)
        d.observe(2, obs)
        assert d.counters() == {"straggler_flags": 0.0,
                                "straggler_host": -1.0}
        d.observe(3, obs)
        assert d.counters() == {"straggler_flags": 1.0,
                                "straggler_host": 2.0}

    def test_recovered_host_resets_streak_and_gauge(self):
        d = StragglerDetector(factor=2.0, patience=1)
        d.observe(1, [{"step_time": 0.1}, {"step_time": 0.1},
                      {"step_time": 0.5}])
        assert d.straggler_host == 2
        d.observe(2, [{"step_time": 0.1}, {"step_time": 0.1},
                      {"step_time": 0.11}])
        assert d.straggler_host == -1
        assert d.straggler_flags == 1  # cumulative count stands

    def test_two_host_fleet_flags_against_peer_median(self):
        # leave-one-out: each host is judged against the median of the
        # OTHER hosts. A fleet median including the straggler's own
        # time would make the 2-host threshold s > s + f — unreachable
        # for any positive peer time.
        d = StragglerDetector(factor=2.0, patience=2)
        obs = [{"step_time": 0.1}, {"step_time": 0.5}]
        d.observe(1, obs)
        assert d.straggler_host == -1  # patience not yet met
        d.observe(2, obs)
        assert d.straggler_host == 1
        assert d.straggler_flags >= 1

    def test_fewer_than_two_hosts_is_no_fleet(self):
        d = StragglerDetector()
        assert d.observe(1, [{"step_time": 0.1}]) is None
        assert d.observe(1, [None, {"step_time": 0.1}, None]) is None

    def test_validation(self):
        with pytest.raises(ValueError, match="factor"):
            StragglerDetector(factor=1.0)
        with pytest.raises(ValueError, match="patience"):
            StragglerDetector(patience=0)


# ---------------------------------------------------------------------------
# 4-host FakeBus: one delayed host surfaces in the straggler report
# ---------------------------------------------------------------------------


@pytest.mark.multihost
def test_fakebus_delayed_host_surfaces_in_straggler_report():
    from scaletorch_tpu.resilience import ResilienceManager
    from scaletorch_tpu.resilience_distributed import CoordinatedResilience
    from tests.test_resilience_distributed import run_hosts

    n, slow_host = 4, 2
    detectors = {}

    def host_fn(i, bus):
        cfg = e2e_cfg(None, sentinel_frequency=1)
        coord = CoordinatedResilience(
            ResilienceManager.from_config(cfg), bus=bus)
        if bus.is_main:
            coord.straggler = StragglerDetector(
                factor=2.0, patience=2, log_frequency=1)
            detectors[i] = coord.straggler
        for step in range(1, 6):
            t0 = time.perf_counter()
            time.sleep(0.08 if i == slow_host else 0.005)  # the "step"
            dt = time.perf_counter() - t0
            _, action = coord.after_step(
                step, {"loss": 1.0},
                telemetry={"step_time": dt, "data_fetch_time": 0.0})
            assert action == "ok"
        return coord.straggler_counters()

    results, errors = run_hosts(n, host_fn)
    assert errors == [None] * n
    det = detectors[0]
    # host 0's report names the delayed host — the fleet-debugging
    # primitive the multihost launcher lacked
    assert det.last_summary["step_time_argmax_host"] == slow_host
    assert results[0]["straggler_host"] == slow_host
    assert results[0]["straggler_flags"] >= 1
    # non-main hosts hold no detector: their counters are empty
    assert results[1] == {}


# ---------------------------------------------------------------------------
# Telemetry facade + config
# ---------------------------------------------------------------------------


class TestFacadeAndConfig:
    def test_disabled_without_dir(self):
        t = Telemetry.from_config(e2e_cfg(None))
        assert not t.enabled
        assert t.tracer is None and t.exporter is None
        assert t.profiler is None and t.snapshotter is None
        assert t.span_tail() == []
        t.export("x", {})  # no-ops
        t.flush()
        t.close()

    def test_enabled_from_config(self, tmp_path):
        cfg = e2e_cfg(None, telemetry_dir=str(tmp_path),
                      profile_on_slow_step=2.0)
        t = Telemetry.from_config(cfg, process_index=1)
        assert t.enabled and t.profiler is not None
        assert t.tracer.path.endswith("trace_proc1.trace.json")
        assert t.exporter.path.endswith("events_proc1.jsonl")
        t.close()

    def test_env_dir_present_wins_including_empty(self, tmp_path,
                                                  monkeypatch):
        cfg = e2e_cfg(None, telemetry_dir=str(tmp_path))
        monkeypatch.setenv("SCALETORCH_TPU_TELEMETRY_DIR", "")
        assert not Telemetry.from_config(cfg).enabled  # explicit off
        monkeypatch.setenv("SCALETORCH_TPU_TELEMETRY_DIR",
                           str(tmp_path / "env"))
        t = Telemetry.from_config(e2e_cfg(None))
        assert t.directory == str(tmp_path / "env")
        t.close()

    def test_config_validation(self, tmp_path):
        for kw in (dict(profile_on_slow_step=0.5),
                   dict(profile_window_steps=0),
                   dict(profile_steps="9:1"),
                   dict(straggler_factor=1.0),
                   dict(straggler_patience=0),
                   dict(log_format="yaml"),
                   dict(ft_slow_step_seconds=0.0),
                   # a profiler with nowhere to write is a config error,
                   # not a silent no-op
                   dict(profile_on_slow_step=2.0),
                   dict(profile_steps="3:5")):
            with pytest.raises(ValueError):
                e2e_cfg(None, **kw)
        # ... and valid with a directory to land in
        e2e_cfg(None, profile_on_slow_step=2.0,
                telemetry_dir=str(tmp_path))


# ---------------------------------------------------------------------------
# --log_format json
# ---------------------------------------------------------------------------


class TestJsonLogFormat:
    def test_json_formatter_wraps_and_passes_through(self):
        import logging

        from scaletorch_tpu.utils.logger import JsonFormatter

        fmt = JsonFormatter(process_index=0)
        rec = logging.LogRecord("n", logging.INFO, "f", 1,
                                "plain message", None, None)
        out = json.loads(fmt.format(rec))
        assert out["msg"] == "plain message"
        assert out["level"] == "INFO" and out["proc"] == 0
        # a metrics step record passes through AS-IS
        rec.structured_record = {"step": 3, "loss": 1.5}
        out = json.loads(fmt.format(rec))
        assert out["step"] == 3 and out["loss"] == 1.5
        assert "msg" not in out

    def test_metrics_line_carries_structured_record(self):
        from scaletorch_tpu.trainer.metrics import MetricsLogger

        ml = MetricsLogger(num_params=10, num_layers=1, num_heads=1,
                           head_dim=8, seq_len=8, tokens_per_step=8,
                           collect_system=False)
        captured = []

        class Cap(logging.Handler):
            def emit(self, r):
                captured.append(r)

        logger = logging.getLogger("scaletorch_tpu")
        handler = Cap(level=logging.INFO)
        logger.addHandler(handler)
        try:
            record = ml.log_step(1, loss=2.0, lr=1e-3, grad_norm=0.5)
        finally:
            logger.removeHandler(handler)
        assert record["loss"] == 2.0
        [logged] = [r for r in captured
                    if getattr(r, "structured_record", None)]
        # the JSON formatter's pass-through payload IS the step record
        assert logged.structured_record["loss"] == 2.0

    def test_get_logger_swaps_to_json_format_process_wide(self, capsys):
        import logging

        from scaletorch_tpu.utils.logger import JsonFormatter, get_logger

        name = "scaletorch_tpu_jsonfmt_test"
        sibling = "scaletorch_tpu_jsonfmt_test.engine"
        logger = get_logger(name)          # text first
        other = get_logger(sibling)        # a module logger, import-time
        try:
            logger = get_logger(name, log_format="json")
            assert all(isinstance(h.formatter, JsonFormatter)
                       for h in logger.handlers)
            # process-wide: the module logger created BEFORE the format
            # switch is reformatted too (fleet aggregation parses the
            # whole stream, not one logger's slice)
            assert all(isinstance(h.formatter, JsonFormatter)
                       for h in other.handlers)
            logger.info("hello")
            line = capsys.readouterr().out.strip().splitlines()[-1]
            assert json.loads(line)["msg"] == "hello"
            # format sticks for later format-less calls, and new loggers
            # adopt it
            assert (get_logger(name)._scaletorch_log_format == "json")
            fresh = get_logger("scaletorch_tpu_jsonfmt_test.late")
            assert all(isinstance(h.formatter, JsonFormatter)
                       for h in fresh.handlers)
        finally:
            get_logger(name, log_format="text")  # restore the global
            for n in (name, sibling, "scaletorch_tpu_jsonfmt_test.late"):
                logging.getLogger(n).handlers.clear()


# ---------------------------------------------------------------------------
# End-to-end: the REAL train loop with telemetry on
# ---------------------------------------------------------------------------


class TelemetryToyTrainer(ToyTrainer):
    """ToyTrainer under the production ``Trainer.step`` (beat sites,
    fetch timing, the ``train_step.*`` spans), so the span timeline
    under test is the production loop's. The toy step takes the
    loader's batch as it is."""

    step = Trainer.__dict__["step"]

    def _device_batch(self, batch):
        return batch


def telemetry_cfg(tmp_path, **kw):
    defaults = dict(
        telemetry_dir=str(tmp_path / "telemetry"),
        log_frequency=1,
        sentinel_frequency=1,
    )
    defaults.update(kw)
    return e2e_cfg(tmp_path, **defaults)


class TestEndToEndTelemetry:
    def test_trace_and_jsonl_from_real_train_loop(self, tmp_path):
        cfg = telemetry_cfg(tmp_path)
        t = TelemetryToyTrainer(cfg, e2e_tokens())
        t.train()
        t.close()
        assert t.global_step == 6

        # Chrome trace: valid JSON, trace-event schema, the span
        # vocabulary of the production loop
        trace_path = os.path.join(
            cfg.telemetry_dir, "trace_proc0.trace.json")
        events = json.load(open(trace_path))
        spans = [e for e in events if e.get("ph") == "X"]
        assert spans, "no spans recorded"
        for e in spans:
            assert {"name", "ts", "dur", "pid", "tid"} <= set(e)
        names = {e["name"] for e in spans}
        assert {"step_boundary", "data_fetch", "step_dispatch",
                "checkpoint_save"} <= names

        # JSONL: schema-valid, ONE train_step record per logged step
        lines = read_jsonl(os.path.join(
            cfg.telemetry_dir, "events_proc0.jsonl"))
        steps = [line for line in lines if line["kind"] == "train_step"]
        assert [s["step"] for s in steps] == [1, 2, 3, 4, 5, 6]
        for s in steps:
            assert s["v"] == SCHEMA_VERSION
            assert np.isfinite(s["loss"])

    def test_injected_slow_step_arms_one_real_profiler_window(
            self, tmp_path):
        """The acceptance drill: --ft_slow_step_at_step spikes one
        step's wall time; the detector arms EXACTLY ONE bounded
        jax.profiler window, written under --telemetry_dir."""
        cfg = telemetry_cfg(
            tmp_path,
            total_train_steps=8,
            ft_slow_step_at_step=3, ft_slow_step_seconds=0.4,
            profile_on_slow_step=3.0, profile_window_steps=2,
        )
        t = TelemetryToyTrainer(cfg, e2e_tokens())
        t.train()
        profiler = t.telemetry.profiler
        t.close()
        assert t.global_step == 8
        assert len(profiler.captures) == 1  # exactly one window
        cap = profiler.captures[0]
        assert cap["trigger"] == "slow_step"
        assert cap["stop_step"] - cap["start_step"] == 2  # bounded
        # the real jax.profiler wrote its capture under telemetry_dir
        assert cap["dir"].startswith(cfg.telemetry_dir)
        captured_files = [
            os.path.join(root, f)
            for root, _, files in os.walk(cap["dir"]) for f in files
        ]
        assert captured_files, "profiler window produced no artifacts"
        # and the window shows the program's own phases and spans beside
        # whatever the device did: nothing else was configured for that
        names = host_span_names(cap["dir"])
        assert {"data_fetch", "step_dispatch", "train_step.place",
                "train_step.dispatch"} <= names, sorted(names)[:40]

    def test_crash_report_embeds_span_timeline_tail(self, tmp_path):
        from scaletorch_tpu.resilience import TrainingDivergedError

        cfg = telemetry_cfg(tmp_path, ft_nan_at_step=3,
                            divergence_policy="abort")
        t = TelemetryToyTrainer(cfg, e2e_tokens())
        with pytest.raises(TrainingDivergedError):
            t.train()
        t.close()
        [report_path] = [
            os.path.join(str(tmp_path / "crash_reports"), f)
            for f in os.listdir(tmp_path / "crash_reports")
        ]
        report = json.load(open(report_path))
        tail = report["span_timeline_tail"]
        assert tail, "crash report carries no span timeline"
        assert {e["name"] for e in tail} >= {"data_fetch", "step_dispatch"}

    def test_engine_metrics_ride_the_same_export_path(self, tmp_path):
        """Serving parity: EngineMetrics snapshots land on the SAME
        schema-versioned JSONL stream, and the engine tick records its
        span vocabulary."""
        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.inference import InferenceEngine, SamplingParams
        from scaletorch_tpu.models import llama

        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, dtype=jnp.float32,
        )
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tracer = SpanTracer(str(tmp_path / "serve.trace.json"), role="serve")
        exporter = TelemetryExporter(str(tmp_path / "serve.jsonl"))
        eng = InferenceEngine(
            params, cfg, max_slots=2, max_seq=16, prefill_len=8,
            page_size=4, sampling=SamplingParams(temperature=0.0),
            tracer=tracer, exporter=exporter, monitor_every=4,
        )
        eng.submit([1, 2, 3], max_new_tokens=5)
        results = eng.run()
        # idle polling must not grow the durable stream: decode_steps is
        # parked, so cadence-multiple ticks export nothing new
        written = exporter.events_written
        for _ in range(5):
            eng.step()
        assert exporter.events_written == written
        # a drain() straight after run() (the common shutdown sequence)
        # makes no progress either — the terminal emit is deduped, not
        # appended as an identical duplicate record
        eng.drain()
        assert exporter.events_written == written
        tracer.close()
        exporter.close()
        assert all(r.outcome == "ok" for r in results.values())
        names = {e["name"] for e in json.load(
            open(tmp_path / "serve.trace.json")) if e.get("ph") == "X"}
        assert {"engine.tick", "engine.tick.sweep", "engine.tick.admit",
                "engine.tick.prefill", "engine.tick.prefill_wait",
                "engine.tick.feed", "engine.tick.decode",
                "engine.tick.decode_wait", "engine.tick.emit",
                "engine.tick.export"} <= names
        assert not {"tick", "admission", "prefill", "decode"} & names
        # (the first tick compiles both steps: on a loaded machine it can
        # pass SLOW_TICK_S and leave a slow_tick record, which is right)
        lines = [line for line in read_jsonl(str(tmp_path / "serve.jsonl"))
                 if line["kind"] != "slow_tick"]
        assert lines and all(
            line["kind"] == "engine_metrics" and line["v"] == SCHEMA_VERSION
            for line in lines)
        # the drain-exit snapshot carries the terminal counters
        assert lines[-1]["requests_ok"] == 1

    def test_engine_tick_span_and_clock_set_is_cheap_when_off(self):
        """The overhead contract of the serving loop, as measured: with
        no profiler session and no tracer, everything a tick adds (the
        outer span with its tick number, every phase with its two clock
        boundaries, the end-of-tick check, the worker loop's spans)
        costs under 1 % of a tick. The shortest tick the ledger shows is
        8.9 ms (serve-1.7b-longgen, PR 28), so the bound is 89 us; the
        loop measures 17 us on an idle core of the test host and 33 us
        with twelve busy processes on its eight cores (the 25 us of wall
        clock this used to assert failed there). The quietest of ten
        short bursts is taken: the other xdist workers share the cores,
        and a burst of 10 ms can fall between their slices where one of
        50 ms could not."""
        import timeit

        import jax
        import jax.numpy as jnp

        from scaletorch_tpu.inference import InferenceEngine, SamplingParams
        from scaletorch_tpu.models import llama

        cfg = llama.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, dtype=jnp.float32,
        )
        eng = InferenceEngine(
            llama.init_params(jax.random.PRNGKey(0), cfg), cfg,
            max_slots=2, max_seq=16, prefill_len=8, page_size=4,
            sampling=SamplingParams(temperature=0.0))
        assert eng.tracer is None
        phases = ("sweep", "admit", "prefill", "prefill_wait", "emit",
                  "feed", "decode", "decode_wait", "emit", "export")
        names = [f"engine.tick.{name}" for name in phases]
        loop = [f"engine.tick_loop.{name}"
                for name in ("inbox", "deliver", "idle")]

        def one_tick():
            t0 = time.monotonic()
            with span("engine.tick", eng.tracer, tick=3):
                for name in names:
                    with eng._phase(name):
                        pass
            eng._close_tick(3, t0)
            for name in loop:
                with span(name, eng.tracer):
                    pass

        one_tick()
        per_tick = min(timeit.repeat(one_tick, number=500, repeat=10)) / 500
        assert per_tick < 0.01 * 8.9e-3, f"{per_tick * 1e6:.1f} us per tick"
        assert eng.metrics.slow_ticks == 0

    def test_disabled_overhead_within_noise(self, tmp_path, monkeypatch):
        """Telemetry off: a step's telemetry work is two inactive
        profiler annotations and a few branches. Held by counts taken
        in this process (no event built, no tracer lock taken, nothing
        exported over a real ``train()``) and by the hooks' best time
        against a ceiling in absolute time: a toy step timed beside
        five other workers is no yardstick (ROADMAP D24)."""
        # (a) the per-step hook cost when disabled: branches, and the
        # two profiler annotations of Trainer.step. About 1 us on an
        # idle core; the ceiling is 25 us, under 0.01 % of the
        # shortest training step the ledger shows (0.67 s)
        tel = Telemetry.disabled()
        coordinator_counters = {}

        def per_step_hooks():
            if tel.tracer is not None:
                tel.tracer.phase("step_boundary")
            with span("train_step.place", tel.tracer):
                pass
            with span("train_step.dispatch", tel.tracer):
                pass
            if tel.profiler is not None:
                tel.profiler.after_step(0, 0.0)
            return {"step_time": 0.0, **coordinator_counters}

        import timeit

        per_call = min(timeit.repeat(
            per_step_hooks, number=2_000, repeat=9)) / 2_000
        assert per_call < 25e-6, f"{per_call * 1e6:.2f} us a step's hooks"

        # (b) the real loop with telemetry off: every span site is the
        # annotation alone (counted here), and the sinks a tracer or an
        # exporter would feed are never reached
        from scaletorch_tpu.telemetry import spans as spans_mod

        calls = {"annotation": 0, "event": 0, "emit": 0, "export": 0}
        real_annotation = spans_mod._annotation

        def counting_annotation(name, args):
            calls["annotation"] += 1
            assert not args, f"{name}: arguments build a string a step"
            return real_annotation(name, args)

        def counted(key):
            def hit(*args, **kwargs):
                calls[key] += 1
            return hit

        monkeypatch.setattr(spans_mod, "_annotation", counting_annotation)
        monkeypatch.setattr(spans_mod._Span, "__init__", counted("event"))
        monkeypatch.setattr(SpanTracer, "_emit", counted("emit"))
        monkeypatch.setattr(TelemetryExporter, "emit", counted("export"))
        cfg = e2e_cfg(None, total_train_steps=40, log_frequency=10_000,
                      sentinel_frequency=0, handle_preemption=False)
        t = TelemetryToyTrainer(cfg, e2e_tokens(128))
        assert not t.telemetry.enabled
        assert t.telemetry.tracer is None and t.telemetry.exporter is None
        t.train(num_steps=8)
        t.close()
        assert calls["event"] == calls["emit"] == calls["export"] == 0
        # two annotations a step (place, dispatch) and nothing that
        # grows faster than the steps do
        assert 16 <= calls["annotation"] <= 16 + 8, calls

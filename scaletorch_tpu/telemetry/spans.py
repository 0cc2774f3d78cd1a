"""Host-side span tracing: one span API, two sinks.

The timeline half of the observability layer: where a MetricsLogger line
says *how fast* a step was, the span stream says *where the time went*
— data fetch vs step dispatch vs checkpoint save on the trainer, the
phases of a tick on the inference engine. ``span(name, tracer)`` is the
one way the program opens a span:

  * it always enters a ``jax.profiler.TraceAnnotation``: with a
    profiler session open (a benchmark's ``--trace 1``, an
    ``AnomalyProfiler`` window, a SIGUSR1 snapshot) the span lands on
    the ``/host:CPU`` plane, on the device events' clock, with nothing
    configured; with no session it is a flag test;
  * with a ``SpanTracer`` attached it also records a Chrome trace event
    (the ``traceEvents`` JSON array format), so a run's timeline loads
    directly in Perfetto (ui.perfetto.dev) or ``chrome://tracing``.

Two contracts every instrumentation site relies on:

  * **off is cheap, not free** — with no session and no tracer a span
    costs one ``TraceAnnotation`` (about 0.3 us on a CPU core); a
    disabled tracer adds one branch. No event dicts, no locks. Hot-path
    spans pass no keyword arguments (each builds a string when a
    session is open).
  * **spans never force a device sync** — span boundaries measure HOST
    time only: the time to *dispatch* work to the accelerator, not to
    complete it. JAX's async dispatch means a ``step_dispatch`` span
    closing in microseconds is healthy (the device is still busy); the
    device-side truth lives in the anomaly profiler's
    ``jax.profiler.trace`` captures (telemetry/profiling.py). No tracer
    method may call ``block_until_ready``, ``float(device_scalar)`` or
    anything else that materialises device values.

Durability: events append to the trace file as they complete (a capped
stream — ``max_events`` bounds the file for week-long runs, with the
drop count recorded in metadata). The file is a valid JSON array after
``close()``; before that it lacks the terminator, which Perfetto
tolerates — so a crashed run's partial trace still loads. Independently
of the file, a small in-memory ``tail()`` of the newest events rides
crash reports and SIGUSR1 live snapshots, so a post-mortem always shows
the final timeline even when the trace file is unreachable.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from collections import deque
from typing import IO, Any, Dict, List, Optional


_trace_annotation = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation``, imported on first use: this
    package stays importable by tooling that runs without jax."""
    global _trace_annotation
    if _trace_annotation is None:
        from jax.profiler import TraceAnnotation

        _trace_annotation = TraceAnnotation
    return _trace_annotation


def _annotation(name: str, args: Dict[str, Any]):
    """A profiler annotation. It starts when it is built, so build it
    at the ``with``."""
    return _annotation_class()(name, **args)


class _BothSinks:
    """One span in the profiler's trace and in a ``SpanTracer``."""

    __slots__ = ("_annotation", "_span")

    def __init__(self, annotation, chrome_span: "_Span") -> None:
        self._annotation = annotation
        self._span = chrome_span

    def __enter__(self) -> "_BothSinks":
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._span.__exit__(*exc)
        self._annotation.__exit__(*exc)

    def set_metadata(self, **args: Any) -> None:
        self._annotation.set_metadata(**args)
        self._span.args = {**(self._span.args or {}), **args}


def span(name: str, tracer: Optional["SpanTracer"] = None, **args: Any):
    """Context manager timing one host-side region (dispatch, not
    device completion — see the module contract): always a profiler
    annotation, and a Chrome event too when ``tracer`` is enabled.
    What ``with`` binds takes ``set_metadata(**args)``: arguments known
    only when the region ends (counts of what it did), in both sinks."""
    annotation = _annotation(name, args)
    if tracer is None or not tracer.enabled:
        return annotation
    return _BothSinks(annotation, _Span(tracer, name, args or None))


class _Span:
    """An open span; closing it (context-manager exit) records the event."""

    __slots__ = ("_tracer", "name", "args", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str,
                 args: Optional[Dict[str, Any]]) -> None:
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = tracer._now_us()

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> None:
        self._tracer._complete(self.name, self._t0, self.args)


class SpanTracer:
    """Low-overhead host-side tracer writing Chrome trace events.

    Three event surfaces:

      * ``span(name, **args)`` — a context manager timing one host-side
        region as a complete event (``ph: "X"``);
      * ``phase(name, step=...)`` — a *phase track*: each call closes the
        previously open phase span and opens the next, so the train
        loop's existing watchdog beat sites (``step_boundary`` /
        ``data_fetch`` / ``step_dispatch`` / ``checkpoint``) double as
        span boundaries and liveness + tracing share one vocabulary;
      * ``counter(name, value)`` — a counter track (``ph: "C"``).

    ``path=None`` keeps the tracer memory-only (tail still collected);
    ``enabled=False`` makes every method a single-branch no-op.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        *,
        process_index: int = 0,
        role: str = "train",
        max_events: int = 200_000,
        tail_size: int = 256,
        enabled: bool = True,
    ) -> None:
        self.enabled = enabled
        self.path = path
        self.process_index = process_index
        self.role = role
        self.max_events = max_events
        self.events_written = 0
        self.events_dropped = 0
        self._tail: deque = deque(maxlen=tail_size)
        # Reentrant: the SIGUSR1 live-snapshot handler runs on the main
        # thread and reads tail() — which must not deadlock when the
        # signal interrupted the same thread mid-_emit.
        self._lock = threading.RLock()
        self._file: Optional[IO[str]] = None
        self._first_event = True
        self._closed = False
        # epoch pairing: ts fields are perf_counter microseconds offset
        # from this origin; wall_time_origin in metadata lets a reader
        # align the trace with log timestamps
        self._origin = time.perf_counter()
        self._wall_origin = time.time()
        self._phase_name: Optional[str] = None
        self._phase_t0 = 0
        self._phase_args: Optional[Dict[str, Any]] = None
        self._phase_annotation: Any = None

    # ---- clock -----------------------------------------------------------
    def _now_us(self) -> int:
        return int((time.perf_counter() - self._origin) * 1e6)

    # ---- public API ------------------------------------------------------
    def span(self, name: str, **args: Any):
        """``span(name, self, **args)``: the module's one span."""
        return span(name, self, **args)

    def phase(self, name: str, step: Optional[int] = None) -> None:
        """Close the open phase span (if any) and start ``name``, in
        both sinks. The trainer's ``_beat`` sites call this, so the
        span vocabulary IS the watchdog phase vocabulary. Call it from
        one thread, outside any ``with span(...)``: the profiler's
        annotations of a thread nest."""
        if not self.enabled:
            return
        self._close_phase()
        self._phase_name = name
        self._phase_t0 = self._now_us()
        self._phase_args = {"step": step} if step is not None else None
        self._phase_annotation = _annotation(name, {})
        self._phase_annotation.__enter__()

    def end_phase(self) -> None:
        """Close the open phase span without starting another (loop
        exit)."""
        if self.enabled:
            self._close_phase()

    def _close_phase(self) -> None:
        if self._phase_name is None:
            return
        self._phase_annotation.__exit__(None, None, None)
        self._phase_annotation = None
        self._emit(self._complete_event(
            self._phase_name, self._phase_t0,
            self._now_us() - self._phase_t0, self._phase_args))
        self._phase_name = None
        self._phase_args = None

    def counter(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        self._emit({
            "name": name, "ph": "C",
            "ts": self._now_us(),
            "pid": self.process_index, "tid": threading.get_native_id(),
            "args": {"value": value},
        })

    # ---- request-scoped async events -------------------------------------
    # Chrome async events ("b"/"n"/"e") are keyed by (cat, id) rather
    # than by thread: every event sharing an id renders on ONE async
    # track no matter which thread emitted it. That is exactly the
    # request-tracing shape — a serving request begins on the gateway's
    # asyncio thread, crosses the EngineWorker bridge, and lives inside
    # the engine tick loop, and its spans must correlate across all
    # three. The id is the request's W3C trace_id
    # (serving/protocol.parse_traceparent), so one Perfetto load shows
    # the whole request next to the per-thread phase spans; the tid
    # still records which thread emitted each event.

    def async_event(self, ph: str, name: str, trace_id: str,
                    **args: Any) -> None:
        """One async event: ``ph`` is ``"b"`` (begin), ``"e"`` (end —
        matched to its begin by (cat, id, name)) or ``"n"``
        (instant)."""
        if not self.enabled:
            return
        if ph not in ("b", "e", "n"):
            raise ValueError(f"async ph must be 'b'/'e'/'n', got {ph!r}")
        self._emit(self._async_event(ph, name, trace_id, args))

    def _async_event(self, ph: str, name: str, trace_id: str,
                     args: Dict[str, Any]) -> dict:
        ev = {
            "name": name, "ph": ph, "cat": "request", "id": str(trace_id),
            "ts": self._now_us(),
            "pid": self.process_index, "tid": threading.get_native_id(),
        }
        if args:
            ev["args"] = args
        return ev

    def tail(self, last_n: Optional[int] = None) -> List[dict]:
        """The newest retained events (crash-report / live-snapshot
        surface); independent of the trace file."""
        with self._lock:
            records = list(self._tail)
        if last_n is not None:
            records = records[-last_n:]
        return records

    def flush(self) -> None:
        if self._file is not None:
            with self._lock:
                if self._file is not None:
                    self._file.flush()

    def close(self) -> None:
        """Finish the open phase and terminate the trace file so it is
        valid JSON. Idempotent; the tracer stays readable (``tail``)
        but records nothing further."""
        self.end_phase()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self.enabled = False
            if self._file is not None:
                if self.events_dropped:
                    # the promised drop record: a reader of a capped
                    # trace can see the timeline is incomplete and by
                    # how much
                    drop = {
                        "name": "events_dropped", "ph": "M",
                        "pid": self.process_index, "tid": 0,
                        "args": {"count": self.events_dropped},
                    }
                    prefix = "" if self._first_event else ",\n"
                    self._file.write(prefix + json.dumps(drop))
                self._file.write("\n]\n")
                self._file.close()
                self._file = None

    # ---- event plumbing --------------------------------------------------
    def _complete(self, name: str, t0_us: int,
                  args: Optional[Dict[str, Any]]) -> None:
        self._emit(self._complete_event(
            name, t0_us, self._now_us() - t0_us, args))

    def _complete_event(self, name: str, ts: int, dur: int,
                        args: Optional[Dict[str, Any]]) -> dict:
        ev = {
            "name": name, "ph": "X", "ts": ts, "dur": max(dur, 0),
            "pid": self.process_index, "tid": threading.get_native_id(),
            "cat": "host",
        }
        if args:
            ev["args"] = args
        return ev

    def _emit(self, event: dict) -> None:
        with self._lock:
            if self._closed:
                return
            self._tail.append(event)
            if self.path is None:
                self.events_written += 1
                return
            if self.events_written >= self.max_events:
                self.events_dropped += 1
                return
            # one write an event, its separator with it: an allocation
            # below may trip a full collection, whose ``host.gc.full``
            # event comes through here on this thread (the lock is
            # reentrant) and must land before or after this one whole
            text = json.dumps(event)
            if self._file is None:
                os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                text = "[\n" + "".join(
                    json.dumps(meta) + ",\n"
                    for meta in self._metadata_events()) + text
                self._file = open(self.path, "w")
            elif not self._first_event:
                text = ",\n" + text
            self._first_event = False
            self._file.write(text)
            self.events_written += 1

    def _metadata_events(self) -> List[dict]:
        return [
            {
                "name": "process_name", "ph": "M", "pid": self.process_index,
                "tid": 0,
                "args": {"name": f"scaletorch-{self.role}"
                                 f"-proc{self.process_index}"},
            },
            {
                "name": "trace_origin", "ph": "M", "pid": self.process_index,
                "tid": 0,
                "args": {"wall_time_origin": self._wall_origin,
                         "clock": "perf_counter_us"},
            },
        ]


class CollectionObserver:
    """What the interpreter's collector costs the process, on
    ``gc.callbacks``: a collection stops every Python thread behind the
    interpreter lock, the engine's tick and the gateway's loop alike,
    and its length follows the heap, not the model. Every collection is
    counted and timed (``time.monotonic`` at ``start`` and at ``stop``,
    both on the thread that tripped it); a collection of the oldest
    generation is also a ``host.gc.full`` span, on the profiler's clock
    through the annotation ``span()`` enters and as a complete event in
    every ``SpanTracer`` handed to ``observe_collections``. Young
    collections (1,000+ a second under load) are summed and never
    spanned: a compare, two clock reads and two adds."""

    __slots__ = ("collections", "pause_s", "full_collections",
                 "full_pause_s", "tracers", "_t0", "_open")

    def __init__(self) -> None:
        self.collections = 0
        self.pause_s = 0.0
        self.full_collections = 0
        self.full_pause_s = 0.0
        self.tracers: List["SpanTracer"] = []
        self._t0 = 0.0
        self._open = None

    def __call__(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            if info["generation"] == OLDEST_GENERATION:
                self._open = _annotation("host.gc.full", {})
                self._open.__enter__()
            self._t0 = time.monotonic()
            return
        if not self._t0:  # put on the list inside a collection
            return
        spent = time.monotonic() - self._t0
        self.collections += 1
        self.pause_s += spent
        if info["generation"] == OLDEST_GENERATION:
            self._full_stop(spent, info)

    def _full_stop(self, spent: float, info: Dict[str, int]) -> None:
        self.full_collections += 1
        self.full_pause_s += spent
        self._open.__exit__(None, None, None)
        self._open = None
        dur_us = int(spent * 1e6)
        for tracer in self.tracers:
            if tracer.enabled:
                tracer._emit(tracer._complete_event(
                    "host.gc.full", tracer._now_us() - dur_us, dur_us,
                    {"collected": info["collected"]}))

    def counters(self) -> Dict[str, float]:
        """Cumulative over the process's life, as the engine's snapshot
        carries them (``host_gc_*``)."""
        return {
            "host_gc_collections": self.collections,
            "host_gc_pause_s": self.pause_s,
            "host_gc_full_collections": self.full_collections,
            "host_gc_full_pause_s": self.full_pause_s,
        }


OLDEST_GENERATION = len(gc.get_threshold()) - 1
_collections = CollectionObserver()


def observe_collections(
        tracer: Optional["SpanTracer"] = None) -> CollectionObserver:
    """The process's one ``CollectionObserver``, on ``gc.callbacks``
    from the first call on (a second call registers nothing);
    ``tracer`` gets the full collections' events from now until it is
    closed."""
    if _collections not in gc.callbacks:
        _annotation_class()  # nothing is imported inside a collection
        gc.callbacks.append(_collections)
    _collections.tracers = [t for t in _collections.tracers if t.enabled]
    if tracer is not None and tracer.enabled \
            and tracer not in _collections.tracers:
        _collections.tracers.append(tracer)
    return _collections


def collection_counters() -> Dict[str, float]:
    """The observer's counters, all 0 until something installs it: a
    reader (``EngineMetrics.snapshot``) installs nothing."""
    return _collections.counters()


def load_trace(path: str) -> List[dict]:
    """Read a trace file back as its event list — accepts both the
    closed (valid JSON) and the crashed (unterminated) form, the same
    leniency Perfetto applies."""
    with open(path) as f:
        text = f.read()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        # unterminated array from a run that never reached close()
        text = text.rstrip().rstrip(",")
        return json.loads(text + "\n]")

"""A token's way from the engine's readback to its socket write: the
stamps of an event (``emitted_t`` at the engine, ``posted_t`` in
``ServingGateway._post``, ``drained_t`` / ``slept_s`` / ``place`` in
``_drain_outbox``, ``written_t`` in ``_write_tokens``), what the access
record makes of them (the five older fields; the four legs of a lag and
what they add to the gaps a 95th percentile stands on), and the
``gateway.deliver`` span. A fake clock and stub writers: every number
below is worked out by hand."""

import math
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import (
    DisaggregatedEngine,
    InferenceEngine,
    SamplingParams,
)
from scaletorch_tpu.models import llama
from scaletorch_tpu.serving import gateway as gateway_mod
from scaletorch_tpu.serving.gateway import (
    HIST_METRICS,
    SHOULDER_FIELDS,
    EngineWorker,
    ServingGateway,
    _delivery_fields,
    _Pending,
)
from scaletorch_tpu.serving.protocol import GenerateRequest
from scaletorch_tpu.serving.remote import RemoteEngineWorker
from scaletorch_tpu.telemetry.spans import SpanTracer

from .fake_replica import FakeEngineWorker
from .test_gateway_outbox import slept
from .test_remote import ServerThread, make_req, run_request

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)
FIELDS = ("deliver_held_s_per_token", "deliver_loop_s_per_token",
          "deliver_lag_p95_s", "emit_gap_p95_s", "write_gap_p95_s")


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    return InferenceEngine(
        params, cfg, max_slots=2, max_seq=32, prefill_len=8,
        sampling=GREEDY, page_size=4, strict_submit=False, **kw)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class FakeLoop:
    def call_soon_threadsafe(self, fn, *args):
        pass


class FakeTransport:
    def __init__(self):
        self.buffered = 0

    def is_closing(self):
        return False

    def get_write_buffer_size(self):
        return self.buffered


class FakeWriter:
    """A stream whose ``drain`` moves the script on: the handler reads
    the clock once an event, before its write."""

    def __init__(self, on_drain=lambda: None):
        self.transport = FakeTransport()
        self.frames = []
        self._on_drain = on_drain

    def write(self, data):
        self.frames.append(data)

    async def drain(self):
        self._on_drain()


class Records:
    def __init__(self):
        self.access = []

    def emit(self, kind, record):
        if kind == "access":
            self.access.append(record)


@pytest.fixture
def clock(monkeypatch):
    clock = FakeClock()
    monkeypatch.setattr(gateway_mod.time, "monotonic", clock)
    monkeypatch.setattr(gateway_mod.time, "sleep", slept)
    return clock


@pytest.fixture(scope="module")
def idle_engine(tiny_llama):
    """One engine for the gateways below: none of them starts its
    worker, so nothing ever runs on it."""
    return make_engine(tiny_llama)


def gateway_over(engine, **kw):
    """A gateway whose (unstarted) worker takes the engine's hook over
    from the gateway of the test before."""
    engine.on_tokens = None
    gateway = ServingGateway(engine, port=0, **kw)
    gateway._loop = FakeLoop()
    return gateway


@pytest.fixture
def gw(idle_engine):
    return gateway_over(idle_engine, exporter=Records())


def pending_request():
    return _Pending(GenerateRequest(prompt=[1, 2], max_new_tokens=64),
                    deadline=None)


def handler_drains(gw, pending, writer):
    """What the request's handler does with its channel, without a
    loop: the channel holds every event and a terminal one, so
    ``_await_terminal`` never suspends."""
    gw._finish_local(pending, "timeout", "end of the script")
    coro = gw._await_terminal(pending, stream=writer)
    with pytest.raises(StopIteration):
        coro.send(None)


def script(admissions):
    """21 token events of one request: when the engine read each back,
    how long the worker held it, how long the loop took to write it.
    A tick of 10 ms; the gaps named hold an admission (0.5 s, 0.4 s);
    gap 12 is the tick's own tail (13 ms); event 5 was held 2 ms where
    1, event 9 waited 6 ms for the loop where 0.5."""
    emit_gaps = [0.010] * 20
    emit_gaps[12] = 0.013
    for gap, seconds in zip(admissions, (0.5, 0.4)):
        emit_gaps[gap] = seconds
    held = [0.001] * 21
    held[5] = 0.002
    loop = [0.0005] * 21
    loop[9] = 0.006
    emitted = [2000.0]
    for gap in emit_gaps:
        emitted.append(emitted[-1] + gap)
    posted = [e + h for e, h in zip(emitted, held)]
    written = [p + w for p, w in zip(posted, loop)]
    return emitted, posted, written


def play(gw, clock, path, emitted, posted, written):
    """The script through the gateway on one of its two write paths."""
    pending = pending_request()
    if path == "outbox":
        pending.stream = FakeWriter()
        for k, (e, p, w) in enumerate(zip(emitted, posted, written)):
            clock.t = p
            gw._post(pending, ("tokens", (7, [k], e)))
            clock.t = w
            gw._drain_outbox()
        assert pending.chan.empty()
    else:
        times = iter(written[1:])
        writer = FakeWriter(
            on_drain=lambda: setattr(clock, "t", next(times, clock.t)))
        writer.transport.buffered = 100  # the socket holds bytes
        pending.stream = writer
        for k, (e, p) in enumerate(zip(emitted, posted)):
            clock.t = p
            gw._post(pending, ("tokens", (7, [k], e)))
        gw._drain_outbox()
        assert writer.frames == []
        clock.t = written[0]
        handler_drains(gw, pending, writer)
    assert len(pending.stream.frames) == len(emitted)
    return pending


@pytest.mark.parametrize("path", ["outbox", "handler"])
@pytest.mark.parametrize("admissions, want", [
    # one admission among 20 gaps: the 95th percentile (rank 19) is the
    # largest gap that holds none
    ((7,), {"emit_gap_p95_s": 0.013, "write_gap_p95_s": 0.0155}),
    # two: rank 19 is the smaller admission
    ((7, 15), {"emit_gap_p95_s": 0.4, "write_gap_p95_s": 0.4}),
])
def test_the_six_fields_of_a_scripted_request(gw, clock, path,
                                              admissions, want):
    emitted, posted, written = script(admissions)
    pending = play(gw, clock, path, emitted, posted, written)
    got = _delivery_fields(pending)
    # 20 events held 1 ms and one 2; 20 written in 0.5 ms and one in 6:
    # 22 and 16 ms over 21 events
    assert got["deliver_held_s_per_token"] == pytest.approx(0.022 / 21)
    assert got["deliver_loop_s_per_token"] == pytest.approx(0.016 / 21)
    # lags: 19 x 1.5 ms, 2.5 (event 5), 7 (event 9): rank 20 of 21
    assert got["deliver_lag_p95_s"] == pytest.approx(0.0025)
    assert got["emit_gap_p95_s"] == pytest.approx(want["emit_gap_p95_s"])
    # the write gaps around event 9 read 10 + 5.5 and 10 - 5.5 ms
    assert got["write_gap_p95_s"] == pytest.approx(want["write_gap_p95_s"])
    assert got["writes_queued"] == (0 if path == "outbox" else 21)
    # the two legs are the lag, and the write gaps are the stream's life
    lags = [w - e for w, e in zip(written, emitted)]
    assert (got["deliver_held_s_per_token"]
            + got["deliver_loop_s_per_token"]
            == pytest.approx(sum(lags) / 21, rel=1e-12))
    stamps = list(pending.written_ts)
    assert stamps == pytest.approx(written, rel=1e-15)
    assert sum(b - a for a, b in zip(stamps, stamps[1:])) == pytest.approx(
        pending.last_token_t - pending.first_token_t, rel=1e-12)
    assert list(pending.emitted_ts) == emitted


def test_the_access_record_carries_the_fields_and_the_histogram(gw, clock):
    emitted, posted, written = script((7,))
    pending = play(gw, clock, "outbox", emitted, posted, written)
    gw._record_outcome(pending, "ok", 200)
    (record,) = gw.exporter.access
    assert {k: record[k] for k in (*FIELDS, *SHOULDER_FIELDS,
                                   "writes_queued")} \
        == _delivery_fields(pending)
    assert record["tokens"] == 21
    assert "deliver_lag" in HIST_METRICS
    lag = gw.hists.merged("deliver_lag")
    assert lag.count == 21 and lag.max == pytest.approx(0.007)
    assert gw.hists.merged("tpot").count == 20
    names = {f["name"] for f in gw.metric_families()}
    assert "request_deliver_lag_seconds" in names


@pytest.mark.parametrize("events", [0, 1])
def test_under_two_token_events_every_field_is_null(gw, clock, events):
    emitted, posted, written = script((7,))
    pending = play(gw, clock, "outbox", emitted[:events], posted[:events],
                   written[:events])
    got = _delivery_fields(pending)
    assert [got[f] for f in (*FIELDS, *SHOULDER_FIELDS)] == [None] * 12
    assert got["writes_queued"] == 0
    gw._record_outcome(pending, "ok", 200)
    assert gw.exporter.access[0]["deliver_lag_p95_s"] is None


def test_a_worker_on_another_clock_leaves_its_three_fields_null(gw, clock):
    """``RemoteEngineWorker`` passes ``emitted_t=None``: the gateway's
    own leg (posted to written) and the write gaps are still read."""
    emitted, posted, written = script((7,))
    pending = play(gw, clock, "outbox", [None] * 21, posted, written)
    got = _delivery_fields(pending)
    assert got["deliver_held_s_per_token"] is None
    assert got["deliver_lag_p95_s"] is None
    assert got["emit_gap_p95_s"] is None
    # no emit gap, no shoulder: which gaps hold an admission is the
    # engine's clock to say
    assert [got[f] for f in SHOULDER_FIELDS] == [None] * 7
    assert got["deliver_loop_s_per_token"] == pytest.approx(0.016 / 21)
    assert got["write_gap_p95_s"] == pytest.approx(0.0155)
    assert len(pending.emitted_ts) == 0
    assert gw.hists.merged("deliver_lag") is None


@pytest.mark.parametrize("stream", [True, False])
def test_writes_queued_counts_the_handler_path_only(gw, clock, stream):
    """Five events written from the outbox, then the socket holds
    bytes for three: those wait for the handler. A unary request has
    no stream: every event is its handler's."""
    pending = pending_request()
    writer = FakeWriter() if stream else None
    pending.stream = writer
    for k in range(8):
        if stream and k == 5:
            writer.transport.buffered = 100
        clock.t += 0.010
        gw._post(pending, ("tokens", (3, [k], clock.t - 0.001)))
        gw._drain_outbox()
    handler_drains(gw, pending, writer)
    assert pending.writes_queued == (3 if stream else 8)
    assert pending.token_count == 8 and len(pending.written_ts) == 8
    if stream:
        assert len(writer.frames) == 8  # order kept: one socket


def deliver_spans(tracer):
    return [e for e in tracer.tail() if e["name"] == "gateway.deliver"]


def test_one_deliver_span_a_batch_with_its_six_arguments(
        idle_engine, clock):
    tracer = SpanTracer()
    gateway = gateway_over(idle_engine, tracer=tracer)
    streams = [pending_request() for _ in range(4)]
    for p in streams:
        p.stream = FakeWriter()
    streams[3].stream.transport.buffered = 100
    clock.t = 1000.0
    gateway._post(streams[0], ("submitted", 0))
    for i, p in enumerate(streams):
        gateway._post(p, ("tokens", (i, [i], clock.t)))
        clock.t += 0.00001
    clock.t = 1000.0002  # the loop wakes 0.2 ms after the first post
    gateway._drain_outbox()
    spans = deliver_spans(tracer)
    assert len(spans) == 1 and spans[0]["ph"] == "X"
    # stream 0 queues behind its own 'submitted', stream 3 behind its
    # socket; 1 and 2 are written back to back: the two arguments that
    # counted the write path's sleeps say there was none
    assert spans[0]["args"] == {"events": 5, "writes": 2, "queued": 2,
                                "pauses": 0, "wake_us": 200,
                                "slept_us": 0}
    assert spans[0]["tid"] == threading.get_native_id()


@pytest.mark.parametrize("streams", [16, 32])
def test_a_full_batchs_deliver_span_counts_no_pause(idle_engine, clock,
                                                    streams):
    """Two ticks 33 ms apart: a cadence at which every boundary between
    two writes was afforded a pause while the write path had any."""
    tracer = SpanTracer()
    gateway = gateway_over(idle_engine, tracer=tracer)
    for k in range(2):
        for slot in range(streams):
            pending = pending_request()
            pending.stream = FakeWriter()
            gateway._post(pending, ("tokens", (slot, [k], clock.t)))
        clock.t += 0.033
        gateway._drain_outbox()
    assert [(e["args"]["writes"], e["args"]["pauses"], e["args"]["slept_us"])
            for e in deliver_spans(tracer)] == [(streams, 0, 0)] * 2


def test_a_batch_with_no_token_event_waited_for_nothing(idle_engine, clock):
    tracer = SpanTracer()
    gateway = gateway_over(idle_engine, tracer=tracer)
    gateway._post(pending_request(), ("submitted", 0))
    clock.t += 0.003
    gateway._drain_outbox()
    (deliver,) = deliver_spans(tracer)
    assert deliver["args"] == {"events": 1, "writes": 0, "queued": 0,
                               "pauses": 0, "wake_us": 0, "slept_us": 0}


# -- the four legs of a lag, event by event -----------------------------------

WRITE_S = 0.0002   # what one event's write keeps the loop


class CostlyWriter(FakeWriter):
    """A write keeps the loop's thread ``WRITE_S``."""

    def __init__(self, clock):
        super().__init__()
        self._clock = clock

    def write(self, data):
        super().write(data)
        self._clock.t += WRITE_S


def legs(pending):
    """(held, wake, pauses, writes) of every token event."""
    return [(p - e, d - p, s, w - d - s) for e, p, d, s, w in zip(
        pending.emitted_ts, pending.posted_ts, pending.drained_ts,
        pending.slept_ss, pending.written_ts)]


def tick(gateway, clock, streams, k, *, emitted, held=0.001, wake=0.0003):
    """One engine step's tokens for ``streams`` (batch order), read
    back at ``emitted``, posted ``held`` later, found by the loop
    ``wake`` after that."""
    clock.t = emitted + held
    for slot, pending in streams:
        gateway._post(pending, ("tokens", (slot, [k], emitted)))
    clock.t += wake
    gateway._drain_outbox()


@pytest.fixture
def paced(gw, clock):
    """The gateway on a clock that writes take time on (a sleep, as
    under every fake clock of this file, is a fault)."""
    def stream():
        pending = pending_request()
        pending.stream = CostlyWriter(clock)
        return pending

    return gw, clock, stream


def assert_the_parts_sum(pending, got):
    for (held, wake, pauses, writes), e, w in zip(
            legs(pending), pending.emitted_ts, pending.written_ts):
        assert held + wake + pauses + writes == pytest.approx(
            w - e, abs=1e-9)
    parts = sum(got[f"write_shoulder_{name}_s"] for name in (
        "emit", "held", "wake", "pauses", "writes"))
    assert parts == pytest.approx(got["write_shoulder_gap_s"], abs=1e-9)


@pytest.mark.parametrize("path", ["outbox", "handler"])
def test_the_four_legs_sum_to_the_lag_and_the_five_parts_to_the_gap(
        gw, clock, path):
    """The older script on both write paths: whatever a leg is called
    where the handler wrote the event, the identity holds."""
    emitted, posted, written = script((7,))
    pending = play(gw, clock, path, emitted, posted, written)
    got = _delivery_fields(pending)
    assert_the_parts_sum(pending, got)
    assert list(pending.posted_ts) == pytest.approx(posted, rel=1e-15)
    if path == "outbox":
        # a batch of one, drained when it is written: the loop's 0.5 ms
        # (6 for event 9) is all wake-up
        assert legs(pending)[9] == pytest.approx(
            (0.001, 0.006, 0.0, 0.0), abs=1e-9)
        assert set(pending.places) == {0}
    else:
        # one batch queued all 21 when the last was posted: they keep
        # its stamps, and what they waited after it is the last leg
        assert set(pending.drained_ts) == {posted[-1]}
        assert list(pending.places) == [0] * 21


def test_a_gap_that_holds_an_admission_is_outside_the_shoulder(gw, clock):
    """20 gaps: 4.5, 9, fourteen of 10, 11, 13, 15.5 ms and one of
    500.5 (the admission). The 90th percentile (rank 18) is 13: on or
    over it and under two median emit gaps stand gap 12 (the tick's
    own tail: 13 ms emitted, 13 written) and gap 8 (10 ms emitted,
    15.5 written: event 9 waited 6 ms for the loop where 0.5)."""
    emitted, posted, written = script((7,))
    pending = play(gw, clock, "outbox", emitted, posted, written)
    got = _delivery_fields(pending)
    assert got["write_shoulder_gap_s"] == pytest.approx(0.01425)
    assert got["write_shoulder_emit_s"] == pytest.approx(0.0115)
    assert got["write_shoulder_wake_s"] == pytest.approx(0.00275)
    assert got["write_shoulder_held_s"] == pytest.approx(0.0, abs=1e-9)
    assert got["write_shoulder_pauses_s"] == 0.0
    assert got["write_shoulder_writes_s"] == pytest.approx(0.0, abs=1e-9)
    assert got["write_shoulder_place_moved_share"] == 0.0
    # the existing percentile reads the same shoulder from above
    assert got["write_gap_p95_s"] == pytest.approx(0.0155)


def test_where_every_long_gap_holds_an_admission_there_is_no_shoulder(
        gw, clock):
    """Three events, an admission in one of two gaps: the 90th
    percentile is the admission and nothing else reaches it."""
    pending = play(gw, clock, "outbox", [2000.0, 2000.01, 2000.51],
                   [2000.001, 2000.011, 2000.511],
                   [2000.0015, 2000.0115, 2000.5115])
    got = _delivery_fields(pending)
    assert [got[f] for f in SHOULDER_FIELDS] == [None] * 7
    assert got["write_gap_p95_s"] == pytest.approx(0.5)


@pytest.mark.parametrize("streams", [4, 16, 32])
def test_the_five_parts_sum_with_a_pauses_leg_of_zero(paced, streams):
    """Twelve ticks, six 10 ms apart and six 33, the loop's wake-up 0.3
    or 0.5 ms by the tick: every event's ``slept_s`` is 0.0, the last
    stream's event is written behind ``streams - 1`` writes and
    nothing else, and the five parts still sum to the gap with a
    ``pauses`` part of exactly 0.0."""
    gw, clock, stream = paced
    batch = [(slot, stream()) for slot in range(streams)]
    emitted = 3000.0
    for k in range(12):
        emitted += 0.010 if k < 6 else 0.033
        tick(gw, clock, batch, k, emitted=emitted,
             wake=0.0005 if k % 2 else 0.0003)
    for slot, pending in batch:
        assert list(pending.slept_ss) == [0.0] * 12
        assert list(pending.places) == [slot] * 12
    last = batch[-1][1]
    assert [writes for *_, writes in legs(last)] == pytest.approx(
        [WRITE_S * (streams - 1)] * 12, abs=1e-9)
    got = _delivery_fields(last)
    assert got["write_shoulder_pauses_s"] == 0.0
    assert got["write_shoulder_writes_s"] == pytest.approx(0.0, abs=1e-9)
    assert got["write_shoulder_place_moved_share"] == 0.0
    assert_the_parts_sum(last, got)


def test_the_access_record_keeps_a_pauses_field_that_reads_zero(paced):
    """``benchmarks/metrics/serve_write_shoulder_pauses_ms.json`` reads
    the field: missing or null it would leave the metric off the
    line, so it stays, a float, 0.0."""
    gw, clock, stream = paced
    batch = [(slot, stream()) for slot in range(4)]
    for k in range(12):
        tick(gw, clock, batch, k, emitted=3000.0 + 0.010 * k,
             wake=0.0006 if k % 3 == 0 else 0.0003)
    gw._record_outcome(batch[3][1], "ok", 200)
    (record,) = gw.exporter.access
    assert record["write_shoulder_pauses_s"] == 0.0
    assert isinstance(record["write_shoulder_pauses_s"], float)
    # the shoulder is the four gaps into a slow wake-up: 10.3 ms
    assert record["write_shoulder_gap_s"] == pytest.approx(0.0103)
    assert record["write_shoulder_wake_s"] == pytest.approx(0.0003)
    assert all(record[f] is not None for f in SHOULDER_FIELDS)


def test_a_place_that_shifts_shows_in_the_writes_leg(paced):
    """A stream behind three others, 21 ticks: the stream ahead of it
    in slot 0 is away in ticks 3-5, 9-11 and 15-17 (retired, then
    another admitted), so the watched event is written
    behind three writes or two. Its 20 gaps: three of 9.8 ms (it moved
    up), fourteen of 10, three of 10.2 (it moved back): rank 18 is
    10.2, and the shoulder is the three gaps in which it moved back."""
    gw, clock, stream = paced
    watched = stream()
    others = [(slot, stream()) for slot in range(3)]
    for k in range(21):
        ahead = others[1:] if (k // 3) % 2 else others
        tick(gw, clock, [*ahead, (3, watched)], k,
             emitted=3000.0 + 0.010 * k)
    assert list(watched.places) == ([3] * 3 + [2] * 3) * 3 + [3] * 3
    assert [writes for *_, writes in legs(watched)] == pytest.approx(
        [WRITE_S * place for place in watched.places], abs=1e-9)
    got = _delivery_fields(watched)
    assert got["write_shoulder_gap_s"] == pytest.approx(0.0102)
    assert got["write_shoulder_writes_s"] == pytest.approx(WRITE_S)
    assert got["write_shoulder_place_moved_share"] == 1.0
    for leg in ("held", "wake", "pauses"):
        assert got[f"write_shoulder_{leg}_s"] == pytest.approx(0.0, abs=1e-9)
    assert_the_parts_sum(watched, got)


def test_an_event_is_written_behind_the_writes_ahead_of_it_and_no_sleep(
        paced):
    gw, clock, stream = paced
    streams = [(slot, stream()) for slot in range(3)]
    tick(gw, clock, streams, 0, emitted=3000.0)
    assert [list(p.slept_ss) for _, p in streams] == [[0.0]] * 3
    held, wake, pauses, writes = legs(streams[2][1])[0]
    assert pauses == 0.0
    assert writes == pytest.approx(2 * WRITE_S, abs=1e-9)
    assert (held, wake) == pytest.approx((0.001, 0.0003), abs=1e-9)


def test_a_queued_event_keeps_its_batchs_stamps(paced):
    """Its socket held bytes: the event goes to the request's handler
    with the batch's stamps as they stood when it was passed on, and
    the handler writes it with them."""
    gw, clock, stream = paced
    late = stream()
    late.stream.transport.buffered = 100
    ahead = [(slot, stream()) for slot in range(3)]
    tick(gw, clock, [*ahead, (3, late)], 0, emitted=3000.0)
    event = late.chan.get_nowait()
    assert event[0] == "tokens"
    # drained at 3000.0013, behind three writes of 0.2 ms, no sleep
    assert event[1][4:] == (pytest.approx(3000.0013), 0.0, 3)
    late.chan.put_nowait(event)
    late.stream.transport.buffered = 0
    handler_drains(gw, late, late.stream)
    assert late.writes_queued == 1
    assert list(late.drained_ts) == pytest.approx([3000.0013])
    assert (list(late.slept_ss), list(late.places)) == ([0.0], [3])


@pytest.mark.parametrize("events", [16, 32])
def test_the_stamps_cost_microseconds_an_event(gw, events):
    """With the profiler off a token event's four more stamps are four
    array appends: a batch of 16 or 32 events through
    ``_drain_outbox`` (stub writers, the real clock) stays under 20 us
    an event in the best of seven rounds, 0.3 % of the shortest tick
    the ledger shows (6.75 ms); what the stamps add to it is under a
    microsecond. An absolute ceiling: no second clock taken under load
    to compare with."""
    import timeit

    streams = [pending_request() for _ in range(events)]
    for p in streams:
        p.stream = FakeWriter()

    def one_tick():
        now = time.monotonic()
        for slot, p in enumerate(streams):
            gw._post(p, ("tokens", (slot, [1], now)))
        gw._drain_outbox()
        for p in streams:
            p.stream.frames.clear()

    one_tick()
    per_event = min(
        timeit.repeat(one_tick, number=50, repeat=7)) / 50 / events
    assert per_event < 20e-6, f"{per_event * 1e6:.1f} us a token event"
    assert len(streams[0].places) == len(streams[0].written_ts) == 351


# -- the hook's new argument, worker by worker -------------------------------

def collect(worker, schedule):
    """Requests through a worker's ``submit``; what ``on_tokens`` got."""
    seen, done = [], threading.Event()
    left = [len(schedule)]

    def on_done(result):
        left[0] -= 1
        if not left[0]:
            done.set()

    for prompt, n in schedule:
        worker.submit(
            GenerateRequest(prompt=prompt, max_new_tokens=n),
            lambda rid, toks, emitted_t: seen.append(
                (rid, emitted_t, time.monotonic())),
            on_done)
    assert done.wait(60)
    return seen


@pytest.mark.parametrize("kind", ["colocated", "disaggregated"])
def test_engine_worker_passes_the_engines_readback_time(tiny_llama, kind):
    cfg, params = tiny_llama
    if kind == "colocated":
        engine = make_engine(tiny_llama)
    else:
        engine = DisaggregatedEngine(
            params, cfg, max_slots=2, max_seq=32, prefill_len=8,
            sampling=GREEDY, page_size=4, disagg_split=(4, 4),
            strict_submit=False)
    worker = EngineWorker(engine, idle_wait_s=0.01).start()
    t0 = time.monotonic()
    try:
        seen = collect(worker, [([1, 2, 3], 5), ([4, 5], 3)])
    finally:
        worker.shutdown()
        worker.join(10)
    assert len(seen) == 8
    for rid in (0, 1):
        stamps = [(e, got) for r, e, got in seen if r == rid]
        # read back before it was handed over, in order, on this clock
        assert all(t0 <= e <= got for e, got in stamps)
        assert [e for e, _ in stamps] == sorted(e for e, _ in stamps)
    # one reading a step: two streams' tokens of one step share it
    assert len({e for _, e, _ in seen}) < len(seen)


def test_the_replica_wire_passes_no_readback_time():
    """The child's ``emitted_t`` is its own monotonic clock: the server
    keeps it off the wire and the remote worker hands on ``None``."""

    class Stamping(FakeEngineWorker):
        def submit(self, req, on_tokens, on_done, **kw):
            super().submit(
                req, lambda rid, toks, _t: on_tokens(rid, toks, 123.0),
                on_done, **kw)

    server = ServerThread(Stamping(token_delay_s=0.0)).start()
    remote = RemoteEngineWorker("127.0.0.1", server.port, replica_id="r0")
    try:
        stamps, done = [], threading.Event()
        remote.submit(make_req([1, 2, 3], 4),
                      lambda rid, toks, emitted_t: stamps.append(emitted_t),
                      lambda result: done.set())
        assert done.wait(30)
        assert stamps == [None] * 4
        out = run_request(remote, make_req([5, 6], 3))
        assert len(out["tokens"]) == 3
    finally:
        server.stop()


def test_nearest_rank_is_the_clients_rule():
    """``benchmarks/lib/trace.percentile``: the smallest sample with at
    least q % at or below it, no interpolation."""
    values = [5.0, 1.0, 4.0, 2.0, 3.0]
    for q, want in [(50, 3.0), (95, 5.0), (20, 1.0), (21, 2.0), (100, 5.0)]:
        assert gateway_mod._nearest_rank(values, q) == want
        rank = max(1, math.ceil(len(values) * q / 100))
        assert sorted(values)[rank - 1] == want

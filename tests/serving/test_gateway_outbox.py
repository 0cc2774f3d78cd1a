"""The worker -> event loop edge of the gateway: a tick's events ride one
wake-up, a streaming response's tokens are written straight from the
outbox, in slot order and back to back (PERF.md, PR 58); the worker
stands aside for the loop once a readback's tokens are posted, for a
bounded time (PERF.md, PR 64); and it hands a tick's results over once
the next decode step is on the device (PERF.md, PR 27)."""

import queue
import threading

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
    EngineWorker,
    ServingGateway,
    _Pending,
)
from scaletorch_tpu.serving.protocol import GenerateRequest, parse_sse_stream

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    return InferenceEngine(
        params, cfg, max_slots=2, max_seq=32, prefill_len=8,
        sampling=SamplingParams(temperature=0.0), page_size=4,
        strict_submit=False, **kw)


def make_blocking_engine(tiny_llama):
    """The engine whose dispatch waits for the step it dispatched."""
    cfg, params = tiny_llama
    return DisaggregatedEngine(
        params, cfg, max_slots=2, max_seq=32, prefill_len=8,
        sampling=SamplingParams(temperature=0.0), page_size=4,
        strict_submit=False, disagg_split=(4, 4))


class FakeLoop:
    def __init__(self):
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append((fn, args))


class FakeTransport:
    def __init__(self, buffered=0, closing=False):
        self.buffered, self.closing = buffered, closing

    def is_closing(self):
        return self.closing

    def get_write_buffer_size(self):
        return self.buffered


class FakeWriter:
    def __init__(self, **kw):
        self.transport = FakeTransport(**kw)
        self.frames = []

    def write(self, data):
        self.frames.append(data)


def slept(seconds):
    """``time.sleep`` on the gateway's write path: a fault."""
    raise AssertionError(f"the write path slept {seconds} s")


def pending_request():
    return _Pending(GenerateRequest(prompt=[1, 2], max_new_tokens=4),
                    deadline=None)


@pytest.fixture
def gw(tiny_llama):
    gateway = ServingGateway(make_engine(tiny_llama), port=0)
    gateway._loop = FakeLoop()
    return gateway


class TestOutbox:
    def test_a_batch_of_events_is_one_wake_up(self, gw):
        a, b = pending_request(), pending_request()
        gw._post(a, ("submitted", 7))
        gw._post(b, ("submitted", 8))
        gw._post(a, ("tokens", (7, [3], None)))
        assert len(gw._loop.calls) == 1
        fn, args = gw._loop.calls[0]
        fn(*args)  # the loop runs the drain
        assert a.chan.get_nowait() == ("submitted", 7)
        kind, payload = a.chan.get_nowait()
        # a token event leaves _post with its stamp behind the worker's
        assert (kind, payload[:3]) == ("tokens", (7, [3], None))
        assert isinstance(payload[3], float)
        assert b.chan.get_nowait() == ("submitted", 8)
        gw._post(b, ("tokens", (8, [5], None)))  # the next batch wakes again
        assert len(gw._loop.calls) == 2

    def test_stream_tokens_are_written_from_the_outbox_in_order(
            self, gw, monkeypatch):
        monkeypatch.setattr(gateway_mod.time, "sleep", slept)
        streams = [pending_request() for _ in range(3)]
        for i, p in enumerate(streams):
            p.stream = FakeWriter()
            gw._post(p, ("tokens", (i, [10 + i], None)))
        gw._drain_outbox()
        for i, p in enumerate(streams):
            assert p.chan.empty()
            events = parse_sse_stream(b"".join(p.stream.frames))
            assert [kind for kind, _ in events] == ["token"]
            assert events[0][1]["request_id"] == i
            assert events[0][1]["token_ids"] == [10 + i]
            assert p.request_id == i and p.token_count == 1
            assert p.first_token_t is not None

    @pytest.mark.parametrize("streams", [2, 8, 16, 32])
    def test_a_batch_is_written_back_to_back_in_slot_order(
            self, gw, monkeypatch, streams):
        """Nothing on the write path may call ``time.sleep``, whatever
        the batch's size and however long ago the batch before it was
        (a first batch ever, then one right behind it)."""
        monkeypatch.setattr(gateway_mod.time, "sleep", slept)
        order = []
        for _ in range(2):
            for slot in range(streams):
                p = pending_request()
                p.stream = FakeWriter()
                p.stream.write = lambda data, slot=slot: order.append(slot)
                gw._post(p, ("tokens", (slot, [1], None)))
            gw._drain_outbox()
        assert order == list(range(streams)) * 2

    @pytest.mark.parametrize("case", [
        "behind_an_event", "socket_backed_up", "closing", "unary"])
    def test_a_token_that_cannot_be_written_at_once_queues(self, gw, case):
        p = pending_request()
        if case == "behind_an_event":
            p.stream = FakeWriter()
            gw._post(p, ("submitted", 4))
        elif case == "socket_backed_up":
            p.stream = FakeWriter(buffered=100)
        elif case == "closing":
            p.stream = FakeWriter(closing=True)
        gw._post(p, ("tokens", (4, [9], None)))
        gw._post(p, ("tokens", (4, [11], None)))
        gw._drain_outbox()
        got = []
        while not p.chan.empty():
            got.append(p.chan.get_nowait())
        assert [e[1][:2] for e in got if e[0] == "tokens"] == [
            (4, [9]), (4, [11])]
        if p.stream is not None:
            assert p.stream.frames == []  # order kept: nothing overtook


class TestTheOutboxSaysWhenItIsDrained:
    """``_outbox_drained``, the event the gateway's in-process workers
    stand aside on: cleared by the post that wakes the loop, set when
    the loop has taken everything posted."""

    def test_the_gateways_workers_wait_on_its_event(self, gw):
        assert gw.workers["r0"].drained is gw._outbox_drained
        assert gw._outbox_drained.is_set()

    def test_a_post_clears_it_and_the_drain_sets_it(self, gw):
        p = pending_request()
        gw._post(p, ("tokens", (1, [3], None)))
        assert not gw._outbox_drained.is_set()
        gw._post(p, ("tokens", (1, [4], None)))
        fn, args = gw._loop.calls[0]
        fn(*args)
        assert gw._outbox_drained.is_set()

    def test_a_post_behind_the_batch_keeps_it_cleared(self, gw):
        """What is posted while the loop writes a batch has asked for a
        drain of its own: the event stays cleared until that one."""
        p, late = pending_request(), pending_request()
        p.stream = FakeWriter()
        p.stream.write = lambda data: gw._post(
            late, ("tokens", (2, [5], None)))
        gw._post(p, ("tokens", (1, [3], None)))
        gw._drain_outbox()
        assert not gw._outbox_drained.is_set() and len(gw._loop.calls) == 2
        gw._drain_outbox()
        assert gw._outbox_drained.is_set()

    def test_a_drain_that_raised_is_made_up_by_the_next(self, gw):
        """A batch whose write raised leaves the event cleared (the
        worker's wait is bounded); the next post asks for a drain of
        its own, and that one sets it."""
        p = pending_request()
        p.stream = FakeWriter()
        p.stream.write = lambda data: 1 / 0
        gw._post(p, ("tokens", (1, [3], None)))
        with pytest.raises(ZeroDivisionError):
            gw._drain_outbox()
        assert not gw._outbox_drained.is_set()
        gw._post(pending_request(), ("tokens", (2, [4], None)))
        assert len(gw._loop.calls) == 2
        gw._drain_outbox()
        assert gw._outbox_drained.is_set()

    def test_set_means_everything_posted_before_was_taken(self, gw):
        """Stress, time-bounded: more posting threads than cores and a
        loop thread that drains, under a short switch interval. A
        thread that posts and then finds the event set must find its
        event written: a set that overtakes a post would let a worker
        go on with its batch still in the outbox."""
        import sys

        written, wakes, faults = set(), queue.SimpleQueue(), []

        def on_loop():
            while (call := wakes.get()) is not None:
                call[0](*call[1])

        gw._loop.call_soon_threadsafe = lambda fn, *args: wakes.put(
            (fn, args))

        def poster(k):
            for n in range(200):
                p = pending_request()
                p.stream = FakeWriter()
                p.stream.write = lambda data, key=(k, n): written.add(key)
                gw._post(p, ("tokens", (k, [n], None)))
                if not gw._outbox_drained.wait(30):
                    faults.append(("never drained", k, n))
                elif (k, n) not in written:
                    faults.append(("set over an undrained post", k, n))

        loop = threading.Thread(target=on_loop, daemon=True)
        posters = [threading.Thread(target=poster, args=(k,), daemon=True)
                   for k in range(32)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            loop.start()
            for t in posters:
                t.start()
            for t in posters:
                t.join(120)
        finally:
            sys.setswitchinterval(interval)
            wakes.put(None)
        loop.join(30)
        assert not any(t.is_alive() for t in posters + [loop])
        assert faults == [] and len(written) == 32 * 200
        assert gw._outbox_drained.is_set() and gw._outbox == []

    def test_a_closed_loop_is_not_waited_for(self, gw):
        def closed(fn, *args):
            raise RuntimeError("Event loop is closed")

        gw._loop.call_soon_threadsafe = closed
        gw._post(pending_request(), ("tokens", (1, [3], None)))
        gw._post(pending_request(), ("tokens", (2, [4], None)))
        assert gw._outbox_drained.is_set()


class StubLoop:
    """The gateway's two ends as the worker sees them, on a thread of
    their own: ``post`` (the worker's thread; the first event of a
    batch clears ``worker.drained`` and wakes the loop) and the drain
    (the loop's thread: takes the batch, then sets ``worker.drained``).
    Everything into one ordered log. ``gate``: what the drain waits on
    before it takes a batch (a slow loop); ``crash``: the loop's thread
    dies at its first batch; ``stop()``: it takes no batch any more."""

    def __init__(self, worker, log, *, gate=None, crash=False):
        self.worker, self.log = worker, log
        self.gate, self.crash = gate, crash
        self.outbox, self.lock = [], threading.Lock()
        self.wakes = queue.SimpleQueue()
        self.first_post = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def post(self, rid, toks, emitted_t):
        self.log.append(("posted", rid))
        with self.lock:
            first = not self.outbox
            self.outbox.append(rid)
            if first:
                self.worker.drained.clear()
        if first:
            self.wakes.put(True)
            self.first_post.set()

    def stop(self):
        self.wakes.put(False)

    def _run(self):
        while self.wakes.get():
            if self.crash:
                return              # the loop's thread is gone
            if self.gate is not None:
                self.gate.wait()
            with self.lock:
                batch, self.outbox = self.outbox, []
            self.log.append(("drained", tuple(batch)))
            self.worker.drained.set()


class Patient(threading.Event):
    """A ``drained`` whose wait takes no notice of the worker's bound
    (half a tick, which on this CPU is anything): long enough for any
    scheduler."""

    def wait(self, timeout=None):
        return super().wait(60.0)


def run_worker_with(tiny_llama, log, *, make=make_engine, patient=False,
                    requests=((1, 2, 3, 6),), **loop_kw):
    """An ``EngineWorker`` whose requests post to a ``StubLoop``; the
    log also has every dispatch of a decode step and every time the
    worker stood aside. Returns (worker, loop, done): ``done`` is set
    at the last terminal result."""
    engine = make(tiny_llama)
    worker = EngineWorker(engine)
    if patient:
        worker.drained = Patient()
        worker.drained.set()
    loop = StubLoop(worker, log, **loop_kw)
    stand_aside = engine.on_handed_over

    def handed_over():
        log.append(("stood_aside", None))
        stand_aside()

    engine.on_handed_over = handed_over
    after_tick = engine.on_dispatched

    def dispatched():
        log.append(("dispatched", None))
        after_tick()

    engine.on_dispatched = dispatched
    done = threading.Event()
    left = [len(requests)]

    def on_done(result):
        log.append(("done", result.request_id, result.outcome))
        left[0] -= 1
        if not left[0]:
            done.set()

    worker.start()
    worker.call_engine(lambda eng: [
        worker.submit(GenerateRequest(prompt=list(prompt), max_new_tokens=n),
                      loop.post, on_done)
        for *prompt, n in requests])
    return worker, loop, done


class TestWorkerStandsAsideForTheLoop:
    def test_the_loops_drain_runs_between_the_readback_and_the_next_dispatch(
            self, tiny_llama):
        """Ordered log, no clock: with the wait long enough for any
        scheduler, every batch a readback posts is drained before the
        worker's thread dispatches the next decode step, and the
        batch is whole (both streams' tokens in one)."""
        log = []
        worker, loop, done = run_worker_with(
            tiny_llama, log, patient=True,
            requests=((1, 2, 3, 8), (4, 5, 6, 8)))
        assert done.wait(120)
        worker.shutdown()
        worker.join(30)
        loop.stop()
        kinds = [e[0] for e in log]
        undrained = False
        for kind in kinds:
            if kind == "posted":
                undrained = True
            elif kind == "drained":
                undrained = False
            elif kind in ("dispatched", "done"):
                assert not undrained, kinds
        batches = [e[1] for e in log if e[0] == "drained"]
        assert batches == [(0, 1)] * 8
        assert kinds.count("stood_aside") == 8

    def test_an_engine_whose_dispatch_blocks_never_stands_aside(
            self, tiny_llama):
        """``DisaggregatedEngine`` behind the worker: a readback finds
        its decode slice idle (the dispatch waited for the step), so
        the worker never gives the interpreter up there. Its steps'
        tokens are posted after a dispatch, where the next dispatch's
        wait lets the loop run, or with their result."""
        log = []
        worker, loop, done = run_worker_with(
            tiny_llama, log, make=make_blocking_engine,
            requests=((1, 2, 3, 8), (4, 5, 6, 8)))
        assert done.wait(120)
        worker.shutdown()
        worker.join(30)
        loop.stop()
        kinds = [e[0] for e in log if e[0] != "drained"]
        assert "stood_aside" not in kinds
        assert kinds.count("posted") == 16
        # the first tokens leave the prefill slice before any step; a
        # step's tokens are posted inside the next dispatch, once the
        # step it waited for has run (those of the last two steps,
        # which no dispatch follows, with the results)
        first = kinds.index("dispatched")
        assert kinds[:first] == ["posted", "posted"]
        steps = [k for k in kinds[first:kinds.index("done")]
                 if k in ("posted", "dispatched")]
        assert steps == (["dispatched"] * 2
                         + ["posted", "posted", "dispatched"] * 5
                         + ["posted"] * 4)

    def test_the_wait_is_half_the_time_since_the_last_one_at_most(
            self, tiny_llama, monkeypatch):
        """Fake clock: in a running loop the bound is half a tick (the
        device is not kept waiting for the event loop, whatever it
        does), after an idle spell the worker's idle wait; with
        nothing undrained the thread does not wait at all."""
        worker = EngineWorker(make_engine(tiny_llama))
        waits = []

        class Undrained:
            def is_set(self):
                return False

            def wait(self, timeout):
                waits.append(timeout)

        worker.drained = Undrained()
        clock = iter([100.0, 100.0068, 100.0318, 107.0, 107.004])
        monkeypatch.setattr(gateway_mod.time, "monotonic",
                            lambda: next(clock))
        for _ in range(4):
            worker._stand_aside()
        assert waits == pytest.approx([0.01, 0.0034, 0.01, 0.01])
        worker.drained = threading.Event()
        worker.drained.set()
        worker._stand_aside()       # returns at once: nothing to wait for
        assert len(waits) == 4

    @pytest.mark.parametrize("loop", ["stopped", "slow", "crashed"])
    def test_a_loop_that_does_not_drain_is_not_waited_for(
            self, tiny_llama, loop):
        """The wait is bounded: a loop that takes no batch (stopped, or
        its thread gone) or takes it late (slow: held until the stream
        has ended) costs the worker its bound a tick and no more. The
        stream runs to its end and the worker shuts down."""
        log = []
        gate = threading.Event()
        worker, stub, done = run_worker_with(
            tiny_llama, log,
            gate=gate if loop != "crashed" else None,
            crash=loop == "crashed")
        assert done.wait(120)
        if loop == "slow":
            gate.set()
        worker.shutdown()
        worker.join(30)
        assert not worker.alive and worker.exit_code == 0
        stub.stop()
        assert [e for e in log if e[0] == "done"] == [("done", 0, "ok")]
        kinds = [e[0] for e in log]
        assert kinds.count("posted") == 6
        # the decode steps went on the device with the batches undrained
        first = kinds.index("posted")
        assert "drained" not in kinds[first:kinds.index("done")]
        assert kinds[first:].count("dispatched") >= 4

    @pytest.mark.parametrize("how", [
        "shutdown", "shutdown_without_drain", "fail", "kill"])
    def test_no_way_of_stopping_the_worker_deadlocks_on_a_dead_loop(
            self, tiny_llama, how):
        """The loop never drains and the worker is stopped mid-stream:
        the thread exits and the stream gets its terminal result."""
        log = []
        worker, stub, done = run_worker_with(
            tiny_llama, log, requests=((1, 2, 3, 24),),
            gate=threading.Event())
        assert stub.first_post.wait(120)
        if how == "shutdown":
            worker.shutdown()
        elif how == "shutdown_without_drain":
            worker.shutdown(drain=False)
        else:
            getattr(worker, how)()
        assert done.wait(120)
        worker.join(30)
        assert not worker.alive
        assert worker.exit_code == (0 if how.startswith("shutdown") else 44)
        assert [e[2] for e in log if e[0] == "done"] == [
            "ok" if how == "shutdown" else "aborted"]
        stub.stop()


class TestWorkerHandsOverBesideTheStep:
    def test_results_and_listeners_wait_for_the_next_decode_step(
            self, tiny_llama):
        engine = make_engine(tiny_llama)
        worker = EngineWorker(engine)
        log = []
        done = threading.Event()

        def phases():
            return set(engine._tick_phase_s)

        worker.tick_listeners.append(
            lambda: log.append(("tick", None, phases())))

        def submit(prompt, n, last=False):
            def on_done(result):
                log.append(("done", result.request_id, phases()))
                if last:
                    done.set()
            worker.submit(
                GenerateRequest(prompt=prompt, max_new_tokens=n),
                lambda rid, toks, _t: log.append(("tokens", rid, phases())),
                on_done)

        worker.start()
        worker.call_engine(lambda eng: (submit([1, 2, 3], 2),
                                        submit([4, 5, 6], 6, last=True)))
        assert done.wait(60)
        worker.shutdown()
        worker.join(10)
        dones = [e for e in log if e[0] == "done"]
        assert [e[1] for e in dones] == [0, 1]
        # the short request ended in a tick that left the other slot
        # decoding: its result was handed over inside the NEXT tick,
        # once that tick's decode step was dispatched
        assert "engine.tick.decode" in dones[0][2]
        assert "engine.tick.emit" not in dones[0][2]
        # the last one left no step to run beside: handed over at once
        assert dones[1][2] == set()
        # every request's tokens came before its result
        for rid in (0, 1):
            idx = [i for i, e in enumerate(log) if e[1] == rid]
            assert log[idx[-1]][0] == "done"
        # listeners: once per tick (the one that read the prefill call,
        # then one a decode step), and once when the worker exits
        ticks = [e for e in log if e[0] == "tick"]
        assert len(ticks) == engine.metrics.decode_steps + 2

    def test_a_cancel_that_empties_the_engine_still_delivers(
            self, tiny_llama):
        """A result held for the next step, and then no next step: the
        other slot was cancelled between two ticks."""
        engine = make_engine(tiny_llama)
        worker = EngineWorker(engine)
        got = {}
        ended = threading.Event()
        first_token = threading.Event()

        def on_done(result):
            got[result.request_id] = result.outcome
            if len(got) == 2:
                ended.set()

        worker.start()
        # both from the worker's own thread, so that one tick admits both
        worker.call_engine(lambda eng: (
            worker.submit(
                GenerateRequest(prompt=[1, 2, 3], max_new_tokens=2),
                lambda rid, toks, _t: None, on_done),
            worker.submit(
                GenerateRequest(prompt=[4, 5, 6], max_new_tokens=24),
                lambda rid, toks, _t: first_token.set(), on_done)))
        assert first_token.wait(60)
        worker.cancel(1, "client gone")
        assert ended.wait(60)
        worker.shutdown()
        worker.join(10)
        assert got == {0: "ok", 1: "aborted"}


class TestArrivalsOfOneLoopTurn:
    def test_an_arrival_a_replica_has_room_for_is_no_backlog(
            self, tiny_llama):
        """Two clients answered by one tick resubmit together. With the
        pool under its watermark the second used to find the first
        still in the queue (the dispatcher's task had not been
        scheduled yet) and was shed for a backlog that was none."""
        from concurrent.futures import ThreadPoolExecutor
        import json
        import urllib.request

        engine = make_engine(tiny_llama)
        # both steps compiled before the clients start: a compile of
        # seconds in the middle of a turn holds the first arrival in the
        # queue for a reason that is not the one under test
        engine.submit([1, 2, 3], max_new_tokens=3)
        engine.run()
        gateway = ServingGateway(engine, port=0)
        gateway.admission._pool_saturated = lambda: True
        gateway.start_in_thread()

        def post(i):
            req = urllib.request.Request(
                f"http://127.0.0.1:{gateway.port}/v1/generate",
                data=json.dumps({"prompt": [1 + i % 5, 2, 3],
                                 "max_new_tokens": 2}).encode(),
                method="POST")
            try:
                # the whole answer: a client that hangs up at the
                # headers leaves a request for the engine to abort
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                    return resp.status
            except urllib.error.HTTPError as err:
                return err.code

        try:
            with ThreadPoolExecutor(2) as pool:
                for _ in range(15):
                    assert list(pool.map(post, range(2))) == [200, 200]
        finally:
            gateway.stop_sync()
        assert gateway.admission.shed_count == 0

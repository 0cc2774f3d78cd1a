"""The worker -> event loop edge of the gateway: a tick's events ride one
wake-up, a streaming response's tokens are written straight from the
outbox, in slot order and back to back (PERF.md, PR 58); and the worker
hands a tick's results over once the next decode step is on the device
(PERF.md, PR 27)."""

import threading

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
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

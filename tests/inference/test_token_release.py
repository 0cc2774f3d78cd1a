"""When the engine hands a step's tokens to ``on_tokens``, and when its
worker hands a tick's results to the event loop: once the next step is
on the device, never between two steps, and a request's tokens always
before its terminal result (PERF.md, PR 27)."""

import jax
import jax.numpy as jnp
import pytest

from scaletorch_tpu.inference import InferenceEngine, SamplingParams
from scaletorch_tpu.models import llama

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
    kw.setdefault("page_size", 4)
    return InferenceEngine(
        params, cfg, max_slots=2, max_seq=32, prefill_len=8,
        sampling=SamplingParams(temperature=0.0), **kw)


class Recorder:
    """``on_tokens`` and ``on_dispatched`` into one ordered log, each
    entry with the phases the tick had been through by then."""

    def __init__(self):
        self.log = []
        self.engine = None

    def tokens(self, slot, request_id, token_ids):
        self.log.append(("tokens", request_id, list(token_ids),
                         set(self.engine._tick_phase_s)))

    def dispatched(self):
        self.log.append(("dispatched", None, None,
                         set(self.engine._tick_phase_s)))


class TestHeldTokens:
    def test_a_decode_steps_tokens_wait_for_the_next_dispatch(
            self, tiny_llama):
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.step()
        # the tick ran a prefill (first token) and a decode step (second):
        # the first was handed over once the decode step was dispatched,
        # the second is held until the next one is
        assert [e[2] for e in rec.log] == [[eng._slots[0].tokens[3]]]
        assert len(eng._held_tokens) == 1
        # ... and it was handed over after the dispatch phase, before the
        # host waited: no emit phase of that decode step had run yet
        assert "engine.tick.decode" in rec.log[0][3]
        assert "engine.tick.export" not in rec.log[0][3]
        eng.step()
        assert len(rec.log) == 2 and len(eng._held_tokens) == 1
        results = eng.run()
        assert [t for e in rec.log for t in e[2]] == results[rid].tokens
        assert eng._held_tokens == []

    def test_a_requests_last_token_comes_with_its_result(
            self, tiny_llama):
        """The stream sees every token, then the terminal result: the
        retiring request's held tokens are handed over when its result
        is recorded, the other slot's stay held."""
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        short = eng.submit([1, 2, 3], max_new_tokens=2)
        long = eng.submit([4, 5, 6], max_new_tokens=8)
        finished = eng.step()  # prefill + one decode: `short` is done
        assert [r.request_id for r in finished] == [short]
        seen_short = [t for e in rec.log if e[1] == short for t in e[2]]
        assert seen_short == finished[0].tokens
        assert [h[1] for h in eng._held_tokens] == [long]

    def test_an_admission_hands_the_held_tokens_over_first(
            self, tiny_llama):
        """No token waits for a prefill call."""
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        first = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        held = list(eng._held_tokens)
        assert held and held[0][1] == first
        before = len(rec.log)
        eng.submit([7, 8, 9, 10], max_new_tokens=4)
        eng.step()
        handed = rec.log[before]
        assert handed[:3] == ("tokens", first, held[0][2])
        assert "engine.tick.prefill" not in handed[3]  # before the call

    def test_an_admissions_read_hands_over_before_it_blocks(
            self, tiny_llama):
        """A tick that admits reads the step in flight with no dispatch
        before it: what the last tick emitted goes out before that wait
        (held through it, 16 streams' tokens came a whole step late and
        together with the next: `serve_itl_p95_ms` 13.9 where 9.3 on a
        v5e), and the read's own tokens before the prefill call."""
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        first = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        held = list(eng._held_tokens)
        assert eng._in_flight is not None and len(held) == 1
        before = len(rec.log)
        eng.submit([7, 8, 9, 10], max_new_tokens=4)
        eng.step()
        early, read = rec.log[before:before + 2]
        assert early[:3] == ("tokens", first, held[0][2])
        assert early[3] == {"engine.tick.sweep"}     # nothing waited yet
        assert read[1] == first
        assert "engine.tick.emit" in read[3]
        assert "engine.tick.prefill" not in read[3]

    def test_on_dispatched_fires_once_per_decode_step_after_the_tokens(
            self, tiny_llama):
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        eng.on_dispatched = rec.dispatched
        eng.submit([1, 2, 3], max_new_tokens=5)
        eng.run()
        kinds = [e[0] for e in rec.log]
        assert kinds.count("dispatched") == eng.metrics.decode_steps
        # each decode step: its predecessor's tokens, then the callback
        first = kinds.index("dispatched")
        assert kinds[first - 1] == "tokens"
        for e in rec.log:
            if e[0] == "dispatched":
                assert "engine.tick.decode" in e[3]

    def test_tokens_are_out_by_the_dispatch_after_their_emit(
            self, tiny_llama):
        """The loop one step ahead: a plain tick dispatches the step
        ahead, hands over what the tick before emitted, and only then
        reads its own step back. So a step's tokens are never held past
        the dispatch that follows their emit, and every hand-over finds
        a step on the device to run beside."""
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        eng.on_dispatched = rec.dispatched
        rid = eng.submit([1, 2, 3], max_new_tokens=10)
        eng.step()
        for _ in range(5):
            assert eng._in_flight is not None
            held = list(eng._held_tokens)
            assert len(held) == 1               # the tick's own token
            before = len(rec.log)
            eng.step()
            handed, dispatched = rec.log[before:]
            assert handed[:3] == ("tokens", rid, held[0][2])
            assert dispatched[0] == "dispatched"
            # after this tick's dispatch, before its read and its emit
            assert "engine.tick.decode" in handed[3]
            assert "engine.tick.emit" not in handed[3]
        results = eng.run()
        assert [t for e in rec.log if e[0] == "tokens"
                for t in e[2]] == results[rid].tokens

    def test_cancel_hands_over_before_the_aborted_result(
            self, tiny_llama):
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        rid = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        assert eng._held_tokens
        assert eng.cancel(rid)
        assert eng._held_tokens == []
        streamed = [t for e in rec.log for t in e[2]]
        assert streamed == eng.result(rid).tokens


def test_without_a_hook_nothing_is_held(tiny_llama):
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    assert eng._held_tokens == []


def test_base_keys_are_uploaded_once_per_admission(tiny_llama):
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=6, seed=5)
    eng.step()
    keys = eng._base_keys_device()
    eng.step()
    assert eng._base_keys_device() is keys  # no upload in a plain tick
    eng.submit([4, 5], max_new_tokens=3, seed=9)
    eng.step()
    assert eng._base_keys_device() is not keys
    assert (jax.device_get(eng._base_keys_device())
            == eng._base_keys).all()

"""When the engine hands a step's tokens to ``on_tokens``, and when its
worker hands a tick's results to the event loop: once the next step is
on the device, never between two steps, and a request's tokens always
before its terminal result (PERF.md, PR 27)."""

import jax
import jax.numpy as jnp
import numpy as np
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
        self.emitted = []
        self.engine = None

    def tokens(self, slot, request_id, token_ids, emitted_t):
        self.log.append(("tokens", request_id, list(token_ids),
                         set(self.engine._tick_phase_s)))
        self.emitted.append((request_id, emitted_t))

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
        # the tick dispatched a prefill call and the step behind it and
        # read the call: its first token is held until the next step
        # is dispatched
        assert rec.log == [] and len(eng._held_tokens) == 1
        eng.step()
        # the first was handed over once that step was dispatched, the
        # second (the read of the step behind the call) is held in turn
        assert [e[2] for e in rec.log] == [[eng._slots[0].tokens[3]]]
        assert len(eng._held_tokens) == 1
        # ... and it was handed over after the dispatch phase, before the
        # host waited: no emit phase of this tick had run yet
        assert "engine.tick.decode" in rec.log[0][3]
        assert "engine.tick.emit" not in rec.log[0][3]
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
        assert eng.step() == []     # the prefill call and its read
        finished = eng.step()       # the step behind it: `short` is done
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

    def test_no_token_is_held_across_the_wait_for_a_prefill_call(
            self, tiny_llama):
        """A tick that admits dispatches the prefill call and the step
        behind it before it reads anything. What the last tick emitted
        goes out before the call is built (held through the tick, 16
        streams' tokens came a whole step late and together with the
        next: `serve_itl_p95_ms` 13.9 where 9.3 on a v5e), and what
        the read of the step in flight emits goes out before the host
        blocks on the call: the work it runs beside is on the device."""
        rec = Recorder()
        eng = make_engine(tiny_llama, on_tokens=rec.tokens)
        rec.engine = eng
        first = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        held = list(eng._held_tokens)
        assert eng._in_flight is not None and len(held) == 1
        before = len(rec.log)
        second = eng.submit([7, 8, 9, 10], max_new_tokens=4)
        eng.step()
        early, read = rec.log[before:]
        assert early[:3] == ("tokens", first, held[0][2])
        assert early[3] == {"engine.tick.sweep"}     # nothing waited yet
        # the step in flight's token: emitted with the call and the
        # next step dispatched, handed over before the call is waited for
        assert read[1] == first
        assert {"engine.tick.prefill", "engine.tick.decode",
                "engine.tick.emit"} <= read[3]
        assert "engine.tick.prefill_wait" not in read[3]
        # the call's own first token waits for the next dispatch
        assert [h[1] for h in eng._held_tokens] == [second]

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


@pytest.mark.parametrize("release", ["next_dispatch", "own_result", "cancel"])
def test_emitted_t_rides_the_held_tuple_unchanged(tiny_llama, release):
    """The hook's fourth argument is the stamp ``_emit`` was handed: it
    sits in the held tuple and comes out as it went in, whichever
    release hands the token over (all of them after a dispatch, or one
    request's through ``_release_tokens(request_id=)``), and it is the
    reading the engine's own TPOT clock took (``last_token_t``)."""
    rec = Recorder()
    eng = make_engine(tiny_llama, on_tokens=rec.tokens)
    rec.engine = eng
    short = eng.submit([1, 2, 3], max_new_tokens=2)
    long = eng.submit([4, 5, 6], max_new_tokens=8)
    eng.step()  # the prefill call, read
    eng.step()  # the step behind it: `short` ended, `long` holds one
    (held,) = eng._held_tokens
    assert held[1] == long and held[3] == eng._slots[1].last_token_t
    # `short`'s two tokens left through its own release, stamps in order
    stamps = [t for rid, t in rec.emitted if rid == short]
    assert len(stamps) == 2 and stamps[0] <= stamps[1] <= held[3]
    before = len(rec.emitted)
    if release == "next_dispatch":
        eng.step()
    elif release == "own_result":
        eng._release_tokens(request_id=long)
    else:
        assert eng.cancel(long)
    assert rec.emitted[before] == (long, held[3])
    assert all(isinstance(t, float) for _, t in rec.emitted)


def test_without_a_hook_nothing_is_held(tiny_llama):
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    assert eng._held_tokens == []


@pytest.mark.parametrize("seed", [
    0, 7, 2**31 - 1, 2**31 + 5, 2**32 - 1, 2**32 + 7, 3300486127, -1, -5,
    2**62 + 11])
def test_the_host_s_key_is_jax_s(seed):
    """The words of ``jax.random.PRNGKey`` for any seed a request may
    carry, made without the device."""
    from scaletorch_tpu.inference.engine import _host_key

    key = _host_key(seed)
    assert key.dtype == np.uint32
    assert (key == np.asarray(jax.random.PRNGKey(seed), np.uint32)).all()


def test_an_admission_makes_its_key_without_the_device(tiny_llama):
    """``_bind_slot`` must not run a program and read it back (an
    admission behind a step in flight would wait for the step):
    ``jax.random.PRNGKey`` is not called while a tick admits."""
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=2, seed=2**31 + 5)
    want = np.asarray(jax.random.PRNGKey(2**31 + 5), np.uint32)
    with pytest.MonkeyPatch.context() as patch:
        patch.delattr(jax.random, "PRNGKey")
        eng.step()
    assert (eng._base_keys[0] == want).all()


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

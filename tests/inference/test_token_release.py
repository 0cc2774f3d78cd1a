"""When the engine hands a step's tokens to ``on_tokens``, and when its
worker hands a tick's results to the event loop: the tokens at the
readback of the step that made them, all of them and before any slot's
retirement is booked (PERF.md, PR 64; until then after the next
dispatch, PR 27); in an engine whose dispatch blocks for its step
(``DisaggregatedEngine``), still after the next dispatch; and a
request's tokens always before its terminal result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import (
    DisaggregatedEngine,
    InferenceEngine,
    SamplingParams,
)
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


def make_blocking(tiny_llama, **kw):
    """The engine whose dispatch waits for the step it dispatched."""
    cfg, params = tiny_llama
    kw.setdefault("page_size", 4)
    return DisaggregatedEngine(
        params, cfg, max_slots=2, max_seq=32, prefill_len=8,
        sampling=SamplingParams(temperature=0.0), disagg_split=(4, 4), **kw)


class Recorder:
    """``on_tokens``, ``on_dispatched``, ``on_handed_over`` and every
    ``_retire_slot`` into one ordered log, each entry with the phases
    the tick had been through by then."""

    def __init__(self, tiny_llama, make=make_engine, **kw):
        self.log = []
        self.emitted = []
        self.engine = eng = make(tiny_llama, on_tokens=self.tokens, **kw)
        eng.on_dispatched = lambda: self.note("dispatched")
        eng.on_handed_over = lambda: self.note("handed_over")
        retire = eng._retire_slot

        def retiring(i, *args, **kwargs):
            self.note("retired", eng._slots[i].request.request_id)
            retire(i, *args, **kwargs)

        eng._retire_slot = retiring

    def note(self, kind, request_id=None, token_ids=None):
        self.log.append((kind, request_id, token_ids,
                         set(self.engine._tick_phase_s)))

    def tokens(self, slot, request_id, token_ids, emitted_t):
        self.note("tokens", request_id, list(token_ids))
        self.emitted.append((request_id, emitted_t))

    def kinds(self, since=0):
        return [e[0] for e in self.log[since:]]

    def streamed(self, request_id):
        return [t for e in self.log
                if e[0] == "tokens" and e[1] == request_id for t in e[2]]


class TestHeldTokens:
    def test_a_decode_steps_tokens_leave_at_its_readback(self, tiny_llama):
        rec = Recorder(tiny_llama)
        eng = rec.engine
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.step()
        # the tick dispatched a prefill call and the step behind it and
        # read the call: its first token left at that readback, with
        # the step behind the call on the device
        assert rec.kinds() == ["dispatched", "tokens", "handed_over"]
        assert rec.log[1][2] == [eng._slots[0].tokens[3]]
        assert "engine.tick.prefill_wait" in rec.log[1][3]
        assert eng._held_tokens == []
        eng.step()
        # the step behind the call, read with the step ahead of it
        # dispatched: its token left in the tick that read it
        assert rec.kinds(3) == ["dispatched", "tokens", "handed_over"]
        assert rec.log[4][2] == [eng._slots[0].tokens[4]]
        # (a phase is booked when it ends: the emit it left in is open)
        assert {"engine.tick.decode",
                "engine.tick.decode_wait"} <= rec.log[4][3]
        assert "engine.tick.emit" not in rec.log[4][3]
        assert eng._held_tokens == []
        eng.step()
        assert len(rec.streamed(rid)) == 3 and eng._held_tokens == []
        results = eng.run()
        assert rec.streamed(rid) == results[rid].tokens
        assert eng._held_tokens == []

    def test_a_requests_last_token_comes_before_its_result(
            self, tiny_llama):
        """The stream sees every token, then the terminal result: the
        retiring request's last token leaves with the step's other
        tokens, and its retirement finds nothing of it held."""
        rec = Recorder(tiny_llama)
        eng = rec.engine
        short = eng.submit([1, 2, 3], max_new_tokens=2)
        long = eng.submit([4, 5, 6], max_new_tokens=8)
        assert eng.step() == []     # the prefill call and its read
        finished = eng.step()       # the step behind it: `short` is done
        assert [r.request_id for r in finished] == [short]
        assert rec.streamed(short) == finished[0].tokens
        retired = rec.kinds().index("retired")
        assert rec.log[retired][1] == short
        assert [e[1] for e in rec.log[:retired] if e[0] == "tokens"] == [
            short, long, short, long]
        assert eng._held_tokens == []

    def test_a_retiring_tick_holds_no_other_streams_token_for_it(
            self, tiny_llama):
        """On the tick that retires a slot every token of the step,
        the other slot's too, reaches ``on_tokens`` (and the consumers
        are let run) before ``_retire_slot`` books the result, the
        page release and the table change."""
        rec = Recorder(tiny_llama)
        eng = rec.engine
        short = eng.submit([1, 2, 3], max_new_tokens=3)
        long = eng.submit([4, 5, 6], max_new_tokens=12)
        eng.step()
        eng.step()
        before = len(rec.log)
        finished = eng.step()       # `short`'s third token: it retires
        assert [r.request_id for r in finished] == [short]
        tick = rec.log[before:]
        assert [(e[0], e[1]) for e in tick] == [
            ("dispatched", None), ("tokens", short), ("tokens", long),
            ("handed_over", None), ("retired", short)]
        assert eng.result(short).tokens == rec.streamed(short)

    def test_nothing_is_held_when_an_admission_is_built(self, tiny_llama):
        """No token waits for a prefill call: what the tick before
        emitted left at its own readback."""
        rec = Recorder(tiny_llama)
        eng = rec.engine
        first = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        assert eng._in_flight is not None and eng._held_tokens == []
        assert len(rec.streamed(first)) == 2
        before = len(rec.log)
        eng.submit([7, 8, 9, 10], max_new_tokens=4)
        eng.step()
        handed = next(e for e in rec.log[before:] if e[0] == "tokens")
        # the step in flight's token, read behind the call's dispatch
        assert handed[1] == first
        assert "engine.tick.prefill" in handed[3]
        assert "engine.tick.prefill_wait" not in handed[3]

    def test_no_token_is_held_across_the_wait_for_a_prefill_call(
            self, tiny_llama):
        """A tick that admits dispatches the prefill call and the step
        behind it before it reads anything. What the read of the step
        in flight emits goes out at that readback, before the host
        blocks on the call (held through the call, 16 streams' tokens
        came a whole call late: `serve_itl_p95_ms` 13.9 where 9.3 on a
        v5e); the call's own first token at ITS readback, the step
        behind the call being on the device."""
        rec = Recorder(tiny_llama)
        eng = rec.engine
        first = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        eng.step()
        before = len(rec.log)
        second = eng.submit([7, 8, 9, 10], max_new_tokens=4)
        eng.step()
        read, call = [e for e in rec.log[before:] if e[0] == "tokens"]
        assert read[1] == first
        assert {"engine.tick.prefill", "engine.tick.decode",
                "engine.tick.decode_wait"} <= read[3]
        assert not {"engine.tick.prefill_wait", "engine.tick.emit"} & read[3]
        assert call[1] == second
        assert {"engine.tick.prefill_wait", "engine.tick.emit"} <= call[3]
        assert rec.kinds(before) == [
            "dispatched", "tokens", "handed_over", "tokens", "handed_over"]
        assert eng._held_tokens == []

    def test_on_dispatched_fires_once_per_decode_step_before_its_ticks_tokens(
            self, tiny_llama):
        rec = Recorder(tiny_llama)
        eng = rec.engine
        eng.submit([1, 2, 3], max_new_tokens=5)
        eng.run()
        kinds = rec.kinds()
        assert kinds.count("dispatched") == eng.metrics.decode_steps
        # each decode step: the callback once it is on the device, then
        # the tokens of what the tick reads behind it
        for k, e in enumerate(rec.log):
            if e[0] == "dispatched":
                assert "engine.tick.decode" in e[3]
                assert "engine.tick.emit" not in e[3]
                assert kinds[k + 1] == "tokens"

    def test_tokens_are_out_before_the_tick_that_read_them_ends(
            self, tiny_llama):
        """The loop one step ahead: a plain tick dispatches the step
        ahead, reads its own step back and hands that step's tokens
        over at once. So no token is held from one tick to the next,
        and every hand-over finds a step on the device to run
        beside."""
        rec = Recorder(tiny_llama)
        eng = rec.engine
        rid = eng.submit([1, 2, 3], max_new_tokens=10)
        eng.step()
        for _ in range(5):
            assert eng._in_flight is not None
            assert eng._held_tokens == []
            before = len(rec.log)
            eng.step()
            dispatched, handed, aside = rec.log[before:]
            assert dispatched[0] == "dispatched"
            assert handed[:3] == ("tokens", rid, [eng._slots[0].tokens[-1]])
            assert aside[0] == "handed_over"
            # after this tick's dispatch and its read, inside its emit
            assert {"engine.tick.decode",
                    "engine.tick.decode_wait"} <= handed[3]
            assert not {"engine.tick.emit", "engine.tick.export"} & handed[3]
        results = eng.run()
        assert rec.streamed(rid) == results[rid].tokens

    def test_an_engine_whose_dispatch_blocks_holds_its_tokens_for_the_dispatch(
            self, tiny_llama):
        """``DisaggregatedEngine`` waits for every step inside its
        dispatch: a readback finds its decode slice idle, so the
        consumers' writes are not let in there. A step's token is held
        through its emit and handed over once the next step has been
        dispatched, and ``on_handed_over`` is never called."""
        rec = Recorder(tiny_llama, make=make_blocking)
        eng = rec.engine
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.step()
        # the first token straight from the prefill slice; a step fed
        # from the host, the step behind it, and the first one read
        assert rec.kinds() == ["tokens", "dispatched", "dispatched"]
        assert len(eng._held_tokens) == 1 and eng._in_flight is not None
        eng.step()
        handed = rec.log[3]
        assert handed[:3] == ("tokens", rid, [eng._slots[0].tokens[4]])
        assert "engine.tick.decode" in handed[3]
        assert "engine.tick.emit" not in handed[3]
        assert rec.kinds(3) == ["tokens", "dispatched"]
        assert len(eng._held_tokens) == 1
        results = eng.run()
        assert rec.streamed(rid) == results[rid].tokens
        assert "handed_over" not in rec.kinds()
        assert eng.metrics.tokens_handed_at_readback == 1
        assert eng.metrics.tokens_handed_later == 5

    def test_cancel_hands_over_before_the_aborted_result(
            self, tiny_llama):
        rec = Recorder(tiny_llama, make=make_blocking)
        eng = rec.engine
        rid = eng.submit([1, 2, 3], max_new_tokens=12)
        eng.step()
        assert eng._held_tokens
        before = len(rec.log)
        assert eng.cancel(rid)
        assert eng._held_tokens == []
        assert rec.kinds(before) == ["retired", "tokens"]  # in _retire_slot,
        assert eng.result(rid) is not None                 # before the result
        assert rec.streamed(rid) == eng.result(rid).tokens

    def test_a_hook_that_raises_mid_batch_loses_and_repeats_nothing(
            self, tiny_llama):
        """The hook raises on the second token of a readback's batch:
        it is disarmed, the token before it was handed over once and
        is not handed over again, nothing stays held for a hook that
        is gone, and every request still ends with all its tokens."""
        seen = []

        def hook(slot, request_id, token_ids, emitted_t):
            seen.append((request_id, list(token_ids)))
            if len(seen) == 4:
                raise RuntimeError("consumer fault")

        eng = make_engine(tiny_llama, on_tokens=hook)
        a = eng.submit([1, 2, 3], max_new_tokens=2)
        b = eng.submit([4, 5, 6], max_new_tokens=6)
        eng.step()                  # both first tokens
        eng.step()                  # the batch it raises in; `a` retires
        assert eng.on_tokens is None and eng._held_tokens == []
        results = eng.run()
        assert len(seen) == 4
        assert [t for rid, toks in seen if rid == a
                for t in toks] == results[a].tokens
        assert [t for rid, toks in seen if rid == b
                for t in toks] == results[b].tokens[:2]
        assert len(results[b].tokens) == 6 and eng._held_tokens == []

    @pytest.mark.parametrize("make", [make_engine, make_blocking])
    def test_the_counters_two_sides_sum_to_the_tokens_generated(
            self, tiny_llama, make):
        rec = Recorder(tiny_llama, make=make)
        eng = rec.engine
        eng.submit([1, 2, 3], max_new_tokens=5)
        eng.submit([4, 5, 6], max_new_tokens=9)
        eng.step()
        eng.submit([7, 8], max_new_tokens=1)
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["tokens_generated"] == 15
        assert (snap["tokens_handed_at_readback"]
                + snap["tokens_handed_later"]) == 15
        assert rec.kinds().count("tokens") == 15
        # an engine whose dispatch blocks: the three first tokens at
        # the prefill slice's readback, a step's after the next
        # dispatch or with the result; the other: none later
        assert snap["tokens_handed_later"] == (
            12 if make is make_blocking else 0)


@pytest.mark.parametrize("release", [
    "readback", "next_dispatch", "own_result", "cancel"])
def test_emitted_t_reaches_the_hook_unchanged(tiny_llama, release):
    """The hook's fourth argument is the stamp ``_emit`` was handed, the
    reading the engine's own TPOT clock took (``last_token_t``): handed
    over at the readback, or out of the held tuple of an engine whose
    dispatch blocks, whichever release hands the token over there (all
    of them after a dispatch, or one request's through
    ``_release_tokens(request_id=)``)."""
    rec = Recorder(tiny_llama, make=(
        make_engine if release == "readback" else make_blocking))
    eng = rec.engine
    rid = eng.submit([4, 5, 6], max_new_tokens=8)
    eng.step()
    stamp = eng._slots[0].last_token_t
    if release == "readback":
        assert eng._held_tokens == []
    else:
        (held,) = eng._held_tokens
        assert held[1] == rid and held[3] == stamp
        assert len(rec.emitted) == 1    # the prefill slice's first token
        if release == "next_dispatch":
            eng.step()
        elif release == "own_result":
            eng._release_tokens(request_id=rid)
        else:
            assert eng.cancel(rid)
    assert rec.emitted[-1] == (rid, stamp)
    assert all(isinstance(t, float) for _, t in rec.emitted)


def test_without_a_hook_nothing_is_held_or_counted(tiny_llama):
    eng = make_engine(tiny_llama)
    eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    assert eng._held_tokens == []
    eng.run()
    assert eng.metrics.tokens_handed_at_readback == 0
    assert eng.metrics.tokens_handed_later == 0


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

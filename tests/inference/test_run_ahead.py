"""The decode loop one step ahead of the host (``InferenceEngine.step``).

With step n on the device the engine dispatches step n+1, fed n's
sampled tokens as the device array they are, and only then reads n
back. The run-ahead crosses an admission: the prefill call goes behind
step n, step n+1 behind the call (the admitted slots' first tokens
merged over n's on the device), and only then is anything read. Quick
tier, CPU. What has to hold: the tokens are the plain forward's, request
for request (tests/inference/oracle.py), whatever kind of cache the
model keeps; what the host learns one step late (an eos, a cancel, a
TTL, a non-finite row of a step or of a prefill call) costs one
slot-step that is thrown away and never surfaces, in tokens or in pages
another request reads; one compiled decode program serves the steps fed
a step's tokens, a prefill call's, both, or the host's; and the
counters say how often the loop ran ahead, how many prefill calls went
behind a step and what was thrown away.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
)
from scaletorch_tpu.models import llama
from tests.inference.oracle import greedy_by_forward, sampled_by_forward
from tests.inference.test_paged_engine import (
    assert_pages_conserved as assert_conserved,
)

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)
SAMPLED = SamplingParams(temperature=1.0, top_k=8)

# six requests of mixed lengths over three slots: the later ones are
# admitted into slots that retire while the others are mid-decode
MIXED = [([1, 2, 3], 9), ([9, 8], 5), ([4, 5, 6, 7], 2), ([11], 12),
         ([1, 2, 3, 5], 7), ([6], 1)]


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 12)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


def greedy(tiny_llama, prompt, n):
    cfg, params = tiny_llama
    return greedy_by_forward(params, cfg, prompt, n)


def count_cold_starts(eng):
    """A list that gets, for every prefill call the engine dispatches
    from here on, whether no decode step was on the device then."""
    cold, tick_device = [], eng._tick_device

    def watched(flight):
        calls = eng.metrics.prefill_calls
        tick_device(flight)
        cold.extend([flight is None] * (eng.metrics.prefill_calls - calls))

    eng._tick_device = watched
    return cold


def first_occurrence(tokens, k):
    """The k-th token (1-based) of a continuation, usable as an eos id
    only if it does not occur before."""
    assert tokens[k - 1] not in tokens[:k - 1], tokens
    return tokens[k - 1]


class TestSameTokens:
    @pytest.mark.parametrize("sampling", [GREEDY, SAMPLED],
                             ids=["greedy", "seeded"])
    def test_mixed_lengths_equal_the_oracle(self, tiny_llama, sampling):
        cfg, params = tiny_llama
        eng = make_engine(tiny_llama, max_slots=3, sampling=sampling)
        ids = [eng.submit(p, max_new_tokens=n, seed=40 + j)
               for j, (p, n) in enumerate(MIXED)]
        results = eng.run()
        for j, ((prompt, n), rid) in enumerate(zip(MIXED, ids)):
            r = results[rid]
            assert (r.outcome, r.finish_reason) == ("ok", "length")
            want = (greedy_by_forward(params, cfg, prompt, n)
                    if sampling.greedy else
                    sampled_by_forward(params, cfg, prompt, n,
                                       seed=40 + j, sampling=sampling))
            assert r.tokens == want, (j, prompt)
        m = eng.metrics
        # the loop did run ahead, and an end by length is known before
        # the step is read: nothing was computed for nobody
        assert m.decode_steps_ahead > m.decode_steps // 2
        assert m.decode_slot_steps_discarded == 0
        assert eng._in_flight is None
        assert eng.decode_compile_count == 1
        assert_conserved(eng)

    def test_one_compile_serves_every_feed(self, tiny_llama):
        """Steps fed a step's tokens, steps fed a prefill call's first
        tokens merged over them (the one behind each admission) and the
        step behind a cold start's call alternate: one call signature,
        and no step is fed from the host."""
        eng = make_engine(tiny_llama, max_slots=2)
        cold = count_cold_starts(eng)
        for prompt, n in MIXED:
            eng.submit(prompt, max_new_tokens=n)
        eng.run()
        m = eng.metrics
        assert m.decode_steps_ahead == m.decode_steps > 0
        assert m.prefill_calls >= 4 and cold == [True] + [False] * (
            m.prefill_calls - 1)
        assert m.prefill_calls_behind_flight == m.prefill_calls - 1
        assert eng.decode_compile_count == 1
        assert eng.prefill_compile_count == len(eng.prefill_shapes)

    def test_no_program_compiles_for_the_step_fed_on_the_device(
            self, tiny_llama):
        """How ``scripts/serve.py`` places an engine on one chip (a
        one-device mesh, parameters and pool committed to it), on a
        device that is not the default one. A caller with host-built
        operands compiles the decode program first, as the benchmark's
        reference check does before it opens its window; after that the
        engine's own steps, fed a step's tokens or a prefill call's
        merged over them on the device, are ONE more call signature and
        NO backend compile: the sampled tokens go back in as a
        host-built operand would."""
        cfg, params = tiny_llama
        device = jax.devices()[3]
        mesh = Mesh(np.array([device]), ("tp",))
        placed = jax.device_put(params, NamedSharding(mesh, P()))
        eng = InferenceEngine(
            placed, cfg, max_slots=2, max_seq=32, prefill_len=12,
            sampling=GREEDY, page_size=4, mesh=mesh)
        compiles, in_decode = [], []

        def on_duration(event, seconds, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                compiles.append(seconds)

        jitted = eng._decode

        def counted(*args):
            before = len(compiles)
            out = jitted(*args)
            in_decode.append(len(compiles) - before)
            return out

        counted._cache_size = jitted._cache_size
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            with eng.on_device():
                _nxt, _logits, _finite, eng.cache = jitted(
                    eng.params, jnp.zeros(2, jnp.int32),
                    jnp.zeros(2, jnp.int32), jnp.zeros(2, bool),
                    jnp.asarray(eng._tables), eng.cache,
                    jnp.zeros((2, 2), jnp.uint32))
                # (jnp.zeros compiled its own little programs too)
                assert compiles and eng.decode_compile_count == 1
                eng._decode = counted
                ids = [eng.submit(p, max_new_tokens=n) for p, n in MIXED]
                results = eng.run()
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)
        assert len(in_decode) == eng.metrics.decode_steps
        assert not any(in_decode)
        assert eng.decode_compile_count == 2    # the check's, the engine's
        assert eng.cache.k.sharding.device_set == {device}
        for (prompt, n), rid in zip(MIXED, ids):
            assert results[rid].tokens == greedy(tiny_llama, prompt, n)
        m = eng.metrics
        assert m.decode_steps_ahead == m.decode_steps > 0
        assert m.prefill_calls_behind_flight >= 3


class TestCounters:
    def test_a_lone_request_by_length(self, tiny_llama):
        """Six tokens: the prefill's and five decode steps'. The first
        step goes behind the prefill call, fed its first token on the
        device, the four after it behind one another; the sixth token
        is known to be the last, so no step follows it."""
        eng = make_engine(tiny_llama)
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        eng.step()
        # the tick dispatched the call and step 1, and read the call
        assert eng.metrics.decode_steps == 1
        assert eng._in_flight is not None and eng._in_flight.number == 1
        assert len(eng._slots[0].tokens) - 3 == 1
        results = eng.run()
        assert len(results[rid].tokens) == 6
        snap = eng.metrics.snapshot()
        assert snap["decode_steps"] == 5
        assert snap["decode_steps_ahead"] == 5
        assert snap["prefill_calls"] == 1
        assert snap["prefill_calls_behind_flight"] == 0     # a cold start
        assert snap["decode_slot_steps_discarded"] == 0
        assert eng._in_flight is None

    def test_an_eos_costs_one_discarded_step(self, tiny_llama):
        """The same request ending by eos at its third token: the step
        after the one that sampled it was already on the device."""
        want = greedy(tiny_llama, [1, 2, 3], 6)
        eos = first_occurrence(want, 3)
        eng = make_engine(tiny_llama)
        rid = eng.submit([1, 2, 3], max_new_tokens=6, eos_id=eos)
        results = eng.run()
        assert results[rid].finish_reason == "eos"
        assert results[rid].tokens == want[:3]
        snap = eng.metrics.snapshot()
        # two steps gave tokens two and three; a third ran for nobody
        assert snap["decode_steps"] == 3
        assert snap["decode_steps_ahead"] == 3
        assert snap["decode_slot_steps_discarded"] == 1
        assert eng._in_flight is None and eng.pending == 0

    def test_a_max_seq_end_is_known_ahead(self, tiny_llama):
        eng = make_engine(tiny_llama, max_seq=8, prefill_len=4)
        rid = eng.submit([1, 2, 3], max_new_tokens=30)
        results = eng.run()
        assert results[rid].finish_reason == "max_seq"
        assert results[rid].tokens == greedy(tiny_llama, [1, 2, 3], 5)
        assert eng.metrics.decode_slot_steps_discarded == 0
        assert eng.metrics.decode_steps == 4

    def test_a_starved_queue_does_not_stop_the_loop(self, tiny_llama):
        """A free slot and a queued request the pool cannot cover yet:
        no admission is due until a slot retires, so the loop keeps
        running ahead, and the request is served once pages return."""
        eng = make_engine(tiny_llama, num_pages=6, prefix_cache=False)
        first = eng.submit([1, 2, 3], max_new_tokens=13)   # 4 pages of 5
        second = eng.submit([9, 8], max_new_tokens=10)     # needs 3
        eng.step()
        assert [r.request_id for r in eng._queue] == [second]
        assert not eng._slots[1].active
        before = eng.metrics.decode_steps_ahead
        for _ in range(4):
            eng.step()
        assert eng.metrics.decode_steps_ahead == before + 4
        results = eng.run()
        assert results[first].tokens == greedy(tiny_llama, [1, 2, 3], 13)
        assert results[second].tokens == greedy(tiny_llama, [9, 8], 10)
        assert_conserved(eng)


class TestLearntLate:
    def test_eos_then_the_slot_goes_to_another_prompt(self, tiny_llama):
        """One slot. A request ends by eos with the next step in flight;
        the queued request, another prompt behind the same first page,
        takes the slot at once. The discarded step's token never
        surfaces, its K/V row lands in pages that were the first
        request's own, and the radix-frozen pages keep their bytes."""
        system = [10, 20, 30, 40, 50, 60, 11, 21]  # two whole pages
        a_prompt, b_prompt = system + [1], system[:4] + [5, 6, 9]
        want_a = greedy(tiny_llama, a_prompt, 8)
        eos = first_occurrence(want_a, 3)
        eng = make_engine(tiny_llama, max_slots=1)
        a = eng.submit(a_prompt, max_new_tokens=8, eos_id=eos)
        b = eng.submit(b_prompt, max_new_tokens=6)
        eng.step()
        frozen = sorted(eng.radix.registered_pages())
        assert len(frozen) == 2
        k_before = np.asarray(eng.cache.k[:, np.asarray(frozen)])
        v_before = np.asarray(eng.cache.v[:, np.asarray(frozen)])
        results = eng.run()
        assert results[a].finish_reason == "eos"
        assert results[a].tokens == want_a[:3]
        assert results[b].tokens == greedy(tiny_llama, b_prompt, 6)
        assert results[b].prefix_hit
        assert eng.metrics.decode_slot_steps_discarded == 1
        assert (np.asarray(eng.cache.k[:, np.asarray(frozen)])
                == k_before).all()
        assert (np.asarray(eng.cache.v[:, np.asarray(frozen)])
                == v_before).all()
        # ... and a third request reads both frozen pages as they were
        c = eng.submit(a_prompt, max_new_tokens=8)
        assert eng.run()[c].tokens == want_a
        assert eng.decode_compile_count == 1
        assert_conserved(eng)

    def test_cancel_with_a_step_in_flight(self, tiny_llama):
        eng = make_engine(tiny_llama)
        gone = eng.submit([1, 2, 3], max_new_tokens=20)
        stays = eng.submit([9, 8, 7, 6], max_new_tokens=12)
        for _ in range(4):
            eng.step()
        assert {i for i, _ in eng._in_flight.bound} == {0, 1}
        assert eng.cancel(gone)
        # the freed slot is taken at once: the prefill call goes behind
        # the step in flight (one row of it for nobody), which writes
        # what it writes before the call does
        late = eng.submit([5, 5], max_new_tokens=5)
        eng.step()
        assert eng.metrics.prefill_calls_behind_flight == 1
        results = eng.run()
        want = greedy(tiny_llama, [1, 2, 3], 20)
        assert results[gone].outcome == "aborted"
        assert results[gone].tokens == want[:len(results[gone].tokens)]
        assert len(results[gone].tokens) == 4    # prefill's + three steps'
        assert results[stays].tokens == greedy(
            tiny_llama, [9, 8, 7, 6], 12)
        assert results[late].tokens == greedy(tiny_llama, [5, 5], 5)
        assert eng.metrics.decode_slot_steps_discarded == 1
        assert_conserved(eng)

    def test_a_deadline_with_a_step_in_flight(self, tiny_llama):
        eng = make_engine(tiny_llama)
        expires = eng.submit([1, 2, 3], max_new_tokens=20, ttl_s=3600.0)
        stays = eng.submit([9, 8, 7, 6], max_new_tokens=9)
        for _ in range(4):      # the prefill call's tick, three steps'
            eng.step()
        assert eng._in_flight is not None
        eng._slots[0].request.deadline = time.monotonic() - 1.0
        results = eng.run()
        assert results[expires].outcome == "timeout"
        assert results[expires].tokens == greedy(
            tiny_llama, [1, 2, 3], 20)[:4]
        assert results[stays].tokens == greedy(tiny_llama, [9, 8, 7, 6], 9)
        assert eng.metrics.decode_slot_steps_discarded == 1
        assert_conserved(eng)

    def test_a_non_finite_row_with_a_step_in_flight(self, tiny_llama):
        """Step 3 is poisoned for slot 0. Step 4 was dispatched before
        step 3 was read: its row for slot 0 is thrown away, the
        quarantine clear follows it on the device, and the slot's next
        tenant reads clean pages."""
        inj = ServingFaultInjector(nan_logits_at_step=3, nan_logits_slot=0)
        eng = make_engine(tiny_llama, injector=inj)
        bad = eng.submit([1, 2, 3], max_new_tokens=10)
        good = eng.submit([9, 8, 7, 6], max_new_tokens=10)
        after = eng.submit([4, 4, 4], max_new_tokens=6)
        results = eng.run()
        assert results[bad].outcome == "quarantined"
        assert results[bad].tokens == greedy(tiny_llama, [1, 2, 3], 10)[:3]
        assert results[good].tokens == greedy(
            tiny_llama, [9, 8, 7, 6], 10)
        assert results[after].tokens == greedy(tiny_llama, [4, 4, 4], 6)
        assert eng.metrics.decode_slot_steps_discarded == 1
        assert eng.decode_compile_count == 1
        assert_conserved(eng)


# ---- across an admission, whatever the cache keeps by slot -------------------

KINDS = ("paged", "delta_rule", "jamba", "window")
POISON = 63     # a prompt that holds it has non-finite prefill logits


@functools.lru_cache(maxsize=None)
def model_of(kind):
    """The toy model each cache kind's own engine tests build."""
    if kind == "paged":
        cfg = llama.LlamaConfig(**TINY)
        return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)
    if kind == "delta_rule":        # kv_cache.HybridCache, a matrix state
        from tests.models.test_olmo_hybrid import seeded_params, tiny_config
    elif kind == "jamba":           # HybridCache, a selective-scan state
        from tests.models.test_jamba import seeded_params, tiny_config
    else:                           # kv_cache.WindowCache: rings by slot
        from tests.inference.test_afmoe_engine import seeded_params
        from tests.models.test_afmoe import tiny_config
    cfg = tiny_config()
    return cfg, seeded_params(cfg)


def engine_of(kind, *, poisonable=False, **kw):
    """Three slots; a prompt and its tokens stay under the oracle's 32
    rows. ``poisonable``: the family's cached forward with non-finite
    logits for a PROMPT that holds ``POISON`` (a decode step's one
    token never does it)."""
    cfg, params = model_of(kind)
    if poisonable:
        from scaletorch_tpu.inference.decode import resolve_forward_cached

        base = resolve_forward_cached(cfg)

        def forward(params, tokens, cfg, cache, **more):
            logits, *rest = base(params, tokens, cfg, cache, **more)
            bad = (tokens.shape[1] > 1) & jnp.any(tokens == POISON, axis=-1)
            return (jnp.where(bad[:, None, None], jnp.nan, logits), *rest)

        kw["forward_fn"] = forward
    return InferenceEngine(
        params, cfg, max_slots=3, max_seq=48, prefill_len=24,
        sampling=GREEDY, page_size=8, **kw)


def ask(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, 60, size=n)] for n in lengths]


def assert_oracle(kind, results, asked):
    """``asked``: request id -> (prompt, tokens asked for)."""
    cfg, params = model_of(kind)
    for rid, (prompt, n) in asked.items():
        assert results[rid].outcome == "ok", results[rid]
        assert results[rid].tokens == greedy_by_forward(
            params, cfg, prompt, n), (rid, prompt)


def assert_settled(eng):
    """What holds after any of it: one decode program, a prefill
    program a listed shape at most, no slot that ran on a stranger's
    state or rings, every page back."""
    assert eng._in_flight is None and eng.pending == 0
    assert eng.decode_compile_count == 1
    assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
    snap = eng.metrics.snapshot()
    assert snap.get("recurrent_state_owner_mismatches", 0) == 0
    assert snap.get("window_slot_reuse_mismatches", 0) == 0
    assert_conserved(eng)


class Since:
    """An engine's counters as their change since this was made."""

    def __init__(self, eng):
        self.eng = eng
        self.at = eng.metrics.snapshot()

    def __getitem__(self, name):
        return self.eng.metrics.snapshot()[name] - self.at[name]


@pytest.fixture(scope="module", params=KINDS)
def served(request):
    """``(kind, engine)``: ONE engine a cache kind for the cases below,
    each of which starts and leaves it drained, so that the compile
    counts at the end of any of them hold after all of it."""
    return request.param, engine_of(request.param)


class TestAcrossAnAdmission:
    def test_admitted_mid_decode_equals_the_oracle(self, served):
        """Eight requests over three slots, ticked by hand: every later
        one is admitted while the others are mid-decode, its prefill
        call behind their step in flight and its first decode step fed
        the call's first token on the device. Every request gets the
        plain forward's tokens; only the first tick's admission (one
        call; three, one a prompt, where a row names its slot) found no
        step on the device; no step was fed from the host."""
        kind, eng = served
        cold, since = count_cold_starts(eng), Since(eng)
        lengths = (5, 17, 9, 3, 21, 12, 7, 14)
        news = (9, 5, 11, 1, 6, 10, 2, 7)
        asked = {eng.submit(p, max_new_tokens=n): (p, n)
                 for p, n in zip(ask(lengths), news)}
        results = {}
        while eng.pending:
            for r in eng.step():
                results[r.request_id] = r
        assert_oracle(kind, results, asked)
        calls = since["prefill_calls"]
        first = 3 if eng._rows_name_slots else 1
        assert calls >= first + 3
        assert cold == [True] * first + [False] * (calls - first)
        assert since["prefill_calls_behind_flight"] == calls - first
        assert since["decode_steps_ahead"] == since["decode_steps"] > 0
        assert since["decode_slot_steps_discarded"] == 0
        # three at once took the full shape, one alone the short row
        assert eng.prefill_compile_count == len(eng.prefill_shapes)
        assert_settled(eng)

    @pytest.mark.parametrize("kind", KINDS)
    def test_a_prefill_row_that_is_not_finite_behind_a_step(self, kind):
        """Two streams mid-decode; a poison prompt is admitted into the
        free slot: its call goes behind step n and step n+1 behind the
        call, with a row for it, before the call is read. At the
        readback it is quarantined, that row of n+1 is thrown away, the
        neighbours' tokens are untouched, and the slot's next tenant
        starts clean."""
        eng = engine_of(kind, poisonable=True)
        a, b, later = ask((6, 11, 8), seed=2)
        asked = {eng.submit(a, max_new_tokens=14): (a, 14),
                 eng.submit(b, max_new_tokens=12): (b, 12)}
        for _ in range(3):
            eng.step()
        bad = eng.submit([4, POISON, 7, 9], max_new_tokens=6)
        eng.step()
        assert eng.result(bad).outcome == "quarantined"
        assert eng.result(bad).tokens == []
        assert "prefill" in eng.result(bad).detail
        # its row of the step behind the call was dispatched all the same
        assert {i for i, _ in eng._in_flight.bound} == {0, 1, 2}
        assert not eng._slots[2].active
        assert eng.metrics.prefill_calls_behind_flight == 1
        asked[eng.submit(later, max_new_tokens=7)] = (later, 7)
        assert_oracle(kind, eng.run(), asked)
        assert eng.metrics.decode_slot_steps_discarded == 1
        assert eng.metrics.requests_admitted == 4
        for buf in eng.cache:
            assert bool(jnp.all(jnp.isfinite(buf)))
        assert_settled(eng)

    def test_an_eos_learnt_after_the_admission_beside_it(self, served):
        """Step n samples a stream's eos. With n unread, a request is
        admitted into ANOTHER, free slot: its call and step n+1 (a row
        for the ending stream too) are on the device when the eos is
        read. That row is thrown away; the admitted request, the other
        stream and the ended slot's next tenant get the oracle's
        tokens."""
        kind, eng = served
        cfg, params = model_of(kind)
        since = Since(eng)
        ending, other, beside, after = ask((7, 12, 10, 5), seed=5)
        free = greedy_by_forward(params, cfg, ending, 10)
        k = next(k for k in range(3, 10) if free[k - 1] not in free[:k - 1])
        a = eng.submit(ending, max_new_tokens=10, eos_id=free[k - 1])
        asked = {eng.submit(other, max_new_tokens=13): (other, 13)}
        for _ in range(k - 1):  # the call's tick, then a token a tick
            eng.step()
        assert len(eng._slots[0].tokens) - len(ending) == k - 1
        asked[eng.submit(beside, max_new_tokens=9)] = (beside, 9)
        (ended,) = eng.step()
        assert (ended.request_id, ended.finish_reason) == (a, "eos")
        assert ended.tokens == free[:k]
        assert since["prefill_calls_behind_flight"] == 1
        assert {i for i, _ in eng._in_flight.bound} == {0, 1, 2}
        asked[eng.submit(after, max_new_tokens=6)] = (after, 6)
        assert_oracle(kind, eng.run(), asked)
        assert since["decode_slot_steps_discarded"] == 1
        assert_settled(eng)

    def test_a_request_of_one_token_has_no_row_behind_its_call(
            self, served):
        kind, eng = served
        since = Since(eng)
        stream, single = ask((9, 13), seed=7)
        asked = {eng.submit(stream, max_new_tokens=8): (stream, 8)}
        eng.step()
        eng.step()
        one = eng.submit(single, max_new_tokens=1)
        (done,) = eng.step()
        assert done.request_id == one
        asked[one] = (single, 1)
        assert {i for i, _ in eng._in_flight.bound} == {0}
        assert_oracle(kind, {**eng.run(), one: done}, asked)
        assert since["decode_slot_steps_discarded"] == 0
        assert since["decode_steps_ahead"] == since["decode_steps"]
        assert_settled(eng)

    def test_two_ticks_in_a_row_that_admit(self, served):
        kind, eng = served
        since = Since(eng)
        first, second, third = ask((8, 15, 4), seed=9)
        asked = {eng.submit(first, max_new_tokens=12): (first, 12)}
        eng.step()
        eng.step()
        asked[eng.submit(second, max_new_tokens=9)] = (second, 9)
        eng.step()
        asked[eng.submit(third, max_new_tokens=10)] = (third, 10)
        eng.step()
        assert since["prefill_calls"] == 3
        assert since["prefill_calls_behind_flight"] == 2
        assert {i for i, _ in eng._in_flight.bound} == {0, 1, 2}
        assert_oracle(kind, eng.run(), asked)
        assert since["decode_steps_ahead"] == since["decode_steps"]
        assert since["decode_slot_steps_discarded"] == 0
        assert_settled(eng)


class TestStopping:
    def test_drain_finishes_what_is_in_flight(self, tiny_llama):
        eng = make_engine(tiny_llama)
        a = eng.submit([1, 2, 3], max_new_tokens=9)
        b = eng.submit([9, 8], max_new_tokens=4)
        queued = eng.submit([5], max_new_tokens=3)
        eng.step()
        eng.step()
        assert eng._in_flight is not None
        results = eng.drain()
        assert results[a].tokens == greedy(tiny_llama, [1, 2, 3], 9)
        assert results[b].tokens == greedy(tiny_llama, [9, 8], 4)
        assert results[queued].outcome == "aborted"
        assert eng._in_flight is None and eng.pending == 0
        assert eng.metrics.decode_slot_steps_discarded == 0
        assert_conserved(eng)

    def test_a_step_budget_drops_the_step_in_flight(self, tiny_llama):
        """``run(max_steps=)`` exhausted with a step on the device: the
        requests end ``aborted`` with the tokens read so far, the step
        is dropped and counted, and the engine serves on."""
        eng = make_engine(tiny_llama)
        a = eng.submit([1, 2, 3], max_new_tokens=20)
        b = eng.submit([9, 8], max_new_tokens=20)
        results = eng.run(max_steps=4)
        assert results[a].outcome == results[b].outcome == "aborted"
        assert results[a].tokens == greedy(tiny_llama, [1, 2, 3], 20)[:4]
        assert results[b].tokens == greedy(tiny_llama, [9, 8], 20)[:4]
        assert eng._in_flight is None
        assert eng.metrics.decode_slot_steps_discarded == 2
        again = eng.submit([1, 2, 3], max_new_tokens=5)
        assert eng.run()[again].tokens == greedy(tiny_llama, [1, 2, 3], 5)
        assert_conserved(eng)

    def test_a_worker_shutdown_drains_with_a_step_in_flight(
            self, tiny_llama):
        """The serving thread's stop: streams end normally, nothing is
        left on the device, every token went through the hook once."""
        import threading

        from scaletorch_tpu.serving.gateway import EngineWorker
        from scaletorch_tpu.serving.protocol import GenerateRequest

        eng = make_engine(tiny_llama)
        worker = EngineWorker(eng, replica_id="r0", idle_wait_s=0.01)
        worker.start()
        streamed, done = {}, {}
        all_done = threading.Event()

        def on_tokens(rid, toks, emitted_t):
            streamed.setdefault(rid, []).extend(toks)

        def on_done(result):
            done[result.request_id] = result
            if len(done) == 2:
                all_done.set()

        worker.submit(GenerateRequest(prompt=[1, 2, 3], max_new_tokens=24),
                      on_tokens, on_done)
        worker.submit(GenerateRequest(prompt=[9, 8], max_new_tokens=17),
                      on_tokens, on_done)
        worker.shutdown(drain=True)
        worker.join(timeout=120)
        assert not worker.alive and worker.exit_code == 0
        assert all_done.is_set()
        by_prompt = {tuple(r.prompt): r for r in done.values()}
        for prompt, n in (([1, 2, 3], 24), ([9, 8], 17)):
            r = by_prompt[tuple(prompt)]
            assert r.outcome == "ok"
            assert r.tokens == greedy(tiny_llama, prompt, n)
            assert streamed[r.request_id] == r.tokens
        assert eng._in_flight is None
        assert eng.metrics.decode_steps_ahead > 0

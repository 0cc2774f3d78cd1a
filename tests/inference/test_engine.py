"""Continuous-batching engine: correctness, no-retrace, TP-sharded cache.

Quick tier, CPU. The no-retrace test is the ISSUE 4 acceptance gate: the
decode step must compile exactly once across a multi-request
continuous-batching run (admissions into freed slots change data, never
shapes).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
)
from scaletorch_tpu.models import llama, qwen3_moe
from tests.inference.oracle import greedy_by_forward

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(params, cfg, **kw):
    """Pages of 4 tokens, so that page boundaries fall inside the
    prompts and generations these tests use."""
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


class TestEngineCorrectness:
    def test_greedy_matches_full_forward_oracle(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=2, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        prompts = [[1, 2, 3], [7, 8, 9, 10]]
        ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        results = eng.run()
        for rid, prompt in zip(ids, prompts):
            assert results[rid].tokens == greedy_by_forward(
                params, cfg, prompt, 6)
            assert results[rid].finish_reason == "length"
            assert results[rid].ttft_s >= 0

    def test_eos_stops_early(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        expected = greedy_by_forward(params, cfg, [1, 2, 3], 6)
        eos = expected[2]  # generation must stop at eos's FIRST occurrence
        rid = eng.submit([1, 2, 3], max_new_tokens=6, eos_id=eos)
        results = eng.run()
        assert results[rid].finish_reason == "eos"
        assert results[rid].tokens == expected[:expected.index(eos) + 1]

    def test_max_seq_caps_generation(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=8,
                          prefill_len=4,
                          sampling=SamplingParams(temperature=0.0))
        rid = eng.submit([1, 2, 3], max_new_tokens=100)
        results = eng.run()
        assert results[rid].finish_reason == "max_seq"
        assert len(results[rid].tokens) + 3 <= 8

    def test_sampled_run_is_seed_deterministic(self, tiny_llama):
        cfg, params = tiny_llama

        def run_once():
            eng = make_engine(
                params, cfg, max_slots=2, max_seq=24, prefill_len=8,
                sampling=SamplingParams(temperature=1.0, top_k=8),
            )
            rid = eng.submit([5, 6], max_new_tokens=5, seed=123)
            return eng.run()[rid].tokens

        assert run_once() == run_once()

    def test_submit_validation(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=4,
                          prefill_len=4)
        with pytest.raises(ValueError, match="at least one token"):
            eng.submit([])
        with pytest.raises(ValueError, match="prefill buffer"):
            eng.submit([1] * 5)
        with pytest.raises(ValueError, match="no room"):
            # fits the prefill buffer but fills max_seq completely
            eng.submit([1] * 4, max_new_tokens=1)


class TestContinuousBatching:
    def test_no_retrace_across_admissions(self, tiny_llama):
        """More requests than slots: later requests are admitted into
        freed slots mid-run; the decode step must have compiled exactly
        once by the end — the jitted step never retraces."""
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=2, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11], [20, 21]]
        lens = [3, 5, 2, 6, 4]
        ids = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, lens)]
        results = eng.run()
        assert eng.decode_compile_count == 1
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert eng.metrics.prefill_calls >= 2  # admissions happened mid-run
        for rid, prompt, n in zip(ids, prompts, lens):
            assert results[rid].tokens == greedy_by_forward(
                params, cfg, prompt, n)

    def test_slot_reuse_does_not_leak_state(self, tiny_llama):
        """A request admitted into a reused slot sees none of the
        previous occupant's cache: its output equals a fresh engine's."""
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        eng.submit([1, 2, 3], max_new_tokens=4)
        second = eng.submit([9, 8, 7], max_new_tokens=4)
        results = eng.run()
        assert results[second].tokens == greedy_by_forward(
            params, cfg, [9, 8, 7], 4)

    def test_metrics_accounting(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=2, max_seq=24,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        eng.submit([1, 2], max_new_tokens=3)
        eng.submit([3, 4], max_new_tokens=5)
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["requests_completed"] == 2
        assert snap["tokens_generated"] == 8
        assert eng.metrics.hist["ttft"].count == 2
        assert snap["queue_depth"] == 0

    def test_metrics_ride_monitor_ring_buffer(self, tiny_llama):
        psutil = pytest.importorskip("psutil")  # noqa: F841
        from scaletorch_tpu.utils.monitor import SystemMonitor

        cfg, params = tiny_llama
        mon = SystemMonitor(max_records=16)
        eng = make_engine(params, cfg, max_slots=1, max_seq=24,
                          prefill_len=8, monitor=mon, monitor_every=1,
                          sampling=SamplingParams(temperature=0.0))
        eng.submit([1, 2], max_new_tokens=4)
        eng.run()
        assert mon.records
        assert "tokens_generated" in mon.records[-1]


class TestTokenHookAndCancel:
    """ISSUE 11 satellites: the streaming bridge's engine surface —
    per-tick ``on_tokens`` push, ``tick()`` driving, ``cancel()``."""

    def test_on_tokens_concatenates_to_final_result_bit_exactly(
            self, tiny_llama):
        cfg, params = tiny_llama
        streamed = {}

        def hook(slot, request_id, token_ids, emitted_t):
            streamed.setdefault(request_id, []).extend(token_ids)

        eng = make_engine(params, cfg, max_slots=2, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0),
                          on_tokens=hook)
        prompts = [[1, 2, 3], [9, 8], [4, 5, 6, 7], [11]]
        ids = [eng.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, [6, 3, 5, 4])]
        results = eng.run()
        for rid in ids:
            assert streamed[rid] == results[rid].tokens  # bit-exact
        assert eng.decode_compile_count == 1  # the hook adds no retrace

    def test_on_tokens_pushed_per_tick_not_at_terminal(self, tiny_llama):
        """The hook must fire DURING generation (push), not once at the
        end: drive tick-by-tick and watch tokens arrive incrementally."""
        cfg, params = tiny_llama
        seen = []
        eng = make_engine(
            params, cfg, max_slots=1, max_seq=32, prefill_len=8,
            sampling=SamplingParams(temperature=0.0),
            on_tokens=lambda s, r, t, _at: seen.extend(t))
        eng.submit([1, 2, 3], max_new_tokens=5)
        counts = []
        while eng.pending:
            eng.tick()
            counts.append(len(seen))
        assert len(seen) == 5
        assert counts == sorted(counts) and len(set(counts)) > 2

    def test_raising_hook_is_disarmed_not_fatal(self, tiny_llama):
        cfg, params = tiny_llama

        def bad_hook(slot, request_id, token_ids, emitted_t):
            raise RuntimeError("consumer bug")

        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0),
                          on_tokens=bad_hook)
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        results = eng.run()
        assert results[rid].outcome == "ok"
        assert eng.on_tokens is None  # disarmed after the first raise

    def test_cancel_queued_and_mid_decode(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        active = eng.submit([1, 2, 3], max_new_tokens=10)
        queued = eng.submit([4, 5], max_new_tokens=10)
        eng.step()                      # admit + first decode of `active`
        assert eng.cancel(queued, detail="client gone")
        finished = eng.step()           # the cancel is delivered this tick
        assert any(r.request_id == queued and r.outcome == "aborted"
                   for r in finished)
        assert eng.cancel(active)
        assert eng.result(active).outcome == "aborted"
        assert eng.result(active).tokens  # partials attached
        assert not eng.cancel(active)   # already terminal
        assert not eng.cancel(12345)    # unknown id
        # conservation holds across cancels
        assert sum(eng.metrics.outcomes.values()) == 2

    def test_cancel_releases_pages(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=20)
        eng.step()
        assert eng.metrics.pages_in_use > 0
        assert eng.cancel(rid)
        eng.allocator.check_conservation()
        # only the radix tree's own references may remain
        assert all(c == 1 for c in eng.allocator._ref.values())

    def test_stop_admissions_without_tick_loop(self, tiny_llama):
        """The bridge-owned drain: stop_admissions() blocks submits but
        the owner keeps ticking in-flight work to completion."""
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8, strict_submit=False,
                          sampling=SamplingParams(temperature=0.0))
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.stop_admissions()
        late = eng.submit([7], max_new_tokens=2)
        assert eng.result(late).outcome == "rejected"
        while eng.pending:
            eng.tick()
        assert eng.result(rid).outcome == "ok"
        assert len(eng.result(rid).tokens) == 4


class TestShardedServing:
    def test_tp_sharded_cache_matches_unsharded(self, tiny_llama, mm_factory):
        """ISSUE 4 acceptance: the TP-sharded cache path runs green on
        the 8-device virtual mesh — params per llama_param_specs, cache
        KV-heads over tp, GSPMD decode — and reproduces the unsharded
        engine's greedy output."""
        from scaletorch_tpu.parallel.tensor_parallel import llama_param_specs

        cfg, params = tiny_llama
        e0 = make_engine(params, cfg, max_slots=2, max_seq=24,
                         prefill_len=8,
                         sampling=SamplingParams(temperature=0.0))
        r0 = e0.submit([1, 2, 3], max_new_tokens=6)
        expected = e0.run()[r0].tokens

        mm = mm_factory(tp=2, dp=4)
        specs = llama_param_specs(cfg, tp_axis="tp")
        shardings = jax.tree.map(
            lambda s: NamedSharding(mm.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        params_sh = jax.tree.map(jax.device_put, params, shardings)
        eng = make_engine(params_sh, cfg, max_slots=2, max_seq=24,
                          prefill_len=8, mesh=mm.mesh, tp_axis="tp",
                          sampling=SamplingParams(temperature=0.0))
        assert eng.cache.k.sharding.spec[2] == "tp"
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        results = eng.run()
        assert results[rid].tokens == expected
        assert eng.decode_compile_count == 1

    def test_qwen3_moe_engine_runs(self):
        """MoE decode through the engine (per-token routing, capacity 1)."""
        cfg = qwen3_moe.Qwen3MoEConfig(
            **{**TINY, "head_dim": 16}, moe_intermediate_size=48,
            num_experts=4, num_experts_per_tok=2, capacity_factor=2.0,
            tie_word_embeddings=False,
        )
        params = qwen3_moe.init_params(jax.random.PRNGKey(0), cfg)
        eng = make_engine(params, cfg, max_slots=2, max_seq=24,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        rid = eng.submit([1, 2, 3], max_new_tokens=5)
        results = eng.run()
        assert len(results[rid].tokens) == 5
        assert results[rid].tokens == greedy_by_forward(
            params, cfg, [1, 2, 3], 5)


class TestRequestScopedObservability:
    """ISSUE 12: trace_id threads through submit into lifecycle spans,
    the terminal result carries latency attribution, and the engine's
    latency histograms fill — all host-side, with greedy outputs and
    the one-compile discipline untouched."""

    TRACE = "0af7651916cd43dd8448eb211c80319c"

    def test_result_latency_attribution_and_histograms(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=2, max_seq=32,
                          prefill_len=8,
                          sampling=SamplingParams(temperature=0.0))
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        result = eng.run()[rid]
        assert result.queue_wait_s is not None and result.queue_wait_s >= 0
        assert result.prefill_s is not None and result.prefill_s > 0
        assert result.prefix_hit is False
        assert result.trace_id is None  # untraced submit stays untraced
        hist = eng.metrics.hist
        assert hist["ttft"].count == 1
        assert hist["queue_wait"].count == 1
        assert hist["prefill"].count == 1
        assert hist["e2e"].count == 1
        assert hist["tpot"].count == 5  # 6 tokens -> 5 inter-arrivals
        state = eng.metrics.histogram_state()
        assert state["e2e"]["count"] == 1
        # distribution sanity: e2e covers ttft
        assert hist["e2e"].quantile(0.5) >= hist["ttft"].min

    def test_trace_id_spans_and_bit_identical_outputs(self, tiny_llama):
        from scaletorch_tpu.telemetry.spans import SpanTracer

        cfg, params = tiny_llama

        def run(tracer, trace_id):
            eng = make_engine(params, cfg, max_slots=2, max_seq=32,
                              prefill_len=8, tracer=tracer,
                              sampling=SamplingParams(temperature=0.0))
            rid = eng.submit([1, 2, 3], max_new_tokens=6,
                             trace_id=trace_id)
            result = eng.run()[rid]
            assert eng.decode_compile_count == 1
            return result

        plain = run(None, None)
        tracer = SpanTracer(path=None, role="serve")  # memory-only
        traced = run(tracer, self.TRACE)
        # instrumentation changes NOTHING functional
        assert traced.tokens == plain.tokens
        assert traced.trace_id == self.TRACE
        ours = [e for e in tracer.tail() if e.get("id") == self.TRACE]
        names = [e["name"] for e in ours]
        for name in ("request", "req.queued", "req.admitted",
                     "req.prefill", "req.decode", "req.finalize"):
            assert name in names, (name, names)
        # balanced async begin/end per span name
        for name in ("request", "req.queued", "req.prefill", "req.decode"):
            phases = [e["ph"] for e in ours if e["name"] == name]
            assert phases == ["b", "e"], (name, phases)
        finalize = [e for e in ours if e["name"] == "req.finalize"][0]
        assert finalize["args"]["outcome"] == "ok"

    def test_rejected_and_cancelled_spans_balance(self, tiny_llama):
        from scaletorch_tpu.telemetry.spans import SpanTracer

        cfg, params = tiny_llama
        tracer = SpanTracer(path=None, role="serve")
        eng = make_engine(params, cfg, max_slots=1, max_seq=16,
                          prefill_len=8, tracer=tracer,
                          strict_submit=False,
                          sampling=SamplingParams(temperature=0.0))
        # rejected at submit: request + queued both close immediately
        bad = eng.submit([], trace_id="11" * 16)
        assert eng.result(bad).outcome == "rejected"
        # cancelled while queued: queued span closes, never decode
        rid = eng.submit([1, 2], max_new_tokens=4, trace_id="22" * 16)
        assert eng.cancel(rid)
        for trace_id in ("11" * 16, "22" * 16):
            ours = [e for e in tracer.tail() if e.get("id") == trace_id]
            for name in ("request", "req.queued"):
                phases = [e["ph"] for e in ours if e["name"] == name]
                assert phases == ["b", "e"], (trace_id, name, phases)
            assert not any(e["name"] == "req.decode" for e in ours)

    def test_unserved_outcomes_stay_out_of_e2e_histogram(self, tiny_llama):
        """Instant rejects and client-cancelled (aborted) slots must
        not feed the e2e tail estimate — only served (ok/timeout)
        requests do."""
        cfg, params = tiny_llama
        eng = make_engine(params, cfg, max_slots=1, max_seq=32,
                          prefill_len=8, strict_submit=False,
                          sampling=SamplingParams(temperature=0.0))
        eng.submit([])  # rejected at submit
        rid = eng.submit([1, 2], max_new_tokens=20)
        eng.step()      # admitted, first token
        assert eng.cancel(rid)  # aborted mid-decode, admit_time set
        ok = eng.submit([1, 2, 3], max_new_tokens=2)
        eng.run()
        assert eng.result(ok).outcome == "ok"
        assert eng.metrics.hist["e2e"].count == 1  # the ok request only

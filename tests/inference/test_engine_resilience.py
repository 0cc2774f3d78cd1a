"""Serving resilience: outcome taxonomy, shedding, quarantine, drain.

Quick tier, CPU. The hermetic end-to-end drills of ISSUE 8: every
submitted request ends in exactly one terminal outcome (the conservation
invariant), injected NaN logits quarantine ONLY the poisoned slot while
the other slots' greedy outputs stay bit-identical to a fault-free run,
deadline/submit storms shed with the correct timeout/shed outcomes,
drain() under SIGTERM finishes in-flight requests, a stalled step fires
the serving watchdog (exit code 44) — and ``decode_compile_count == 1``
holds through all of it.
"""

import json
import os
import random
import signal
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import (
    SERVING_STALL_EXIT_CODE,
    TERMINAL_OUTCOMES,
    EngineDraining,
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
    make_paged_prefill_step,
    make_serving_watchdog,
)
from scaletorch_tpu.models import llama
from scaletorch_tpu.resilience import PreemptionHandler
from tests.inference.oracle import greedy_by_forward

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def make_engine(tiny_llama, **kw):
    cfg, params = tiny_llama
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("sampling", GREEDY)
    # pages of 4 tokens: page boundaries fall inside these prompts
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


def assert_conserved(eng):
    """The ISSUE 8 conservation invariant: every submitted request has
    exactly one terminal result, no slot stays active past its request's
    terminal outcome, and the compiled steps never retraced."""
    m = eng.metrics
    assert m.requests_submitted == sum(m.outcomes.values())
    assert m.requests_submitted == len(eng._results)
    assert all(r.outcome in TERMINAL_OUTCOMES for r in eng._results.values())
    assert not any(s.active for s in eng._slots)
    assert eng.pending == 0
    assert eng.decode_compile_count <= 1
    assert eng.prefill_compile_count <= len(eng.prefill_shapes)


class TestOutcomeTaxonomy:
    def test_run_exhaustion_returns_partials_as_aborted(self, tiny_llama):
        """Satellite: run(max_steps) must return the completed work and
        mark the unfinished requests aborted — not raise away finished
        results."""
        eng = make_engine(tiny_llama, max_slots=1)
        done = eng.submit([1, 2, 3], max_new_tokens=2)
        hung = eng.submit([7, 8], max_new_tokens=25)   # needs ~25 steps
        results = eng.run(max_steps=6)
        cfg, params = tiny_llama
        assert results[done].outcome == "ok"
        assert results[done].tokens == greedy_by_forward(
            params, cfg, [1, 2, 3], 2)
        assert results[hung].outcome == "aborted"
        assert results[hung].finish_reason == "aborted"
        assert len(results[hung].tokens) > 0     # partials attached
        assert "exhausted" in results[hung].detail
        assert_conserved(eng)

    def test_strict_submit_still_raises(self, tiny_llama):
        """Backward compatibility: the default engine raises on invalid
        prompts exactly as before."""
        eng = make_engine(tiny_llama, max_slots=1, max_seq=4, prefill_len=4)
        with pytest.raises(ValueError, match="at least one token"):
            eng.submit([])
        with pytest.raises(ValueError, match="prefill buffer"):
            eng.submit([1] * 5)
        with pytest.raises(ValueError, match="no room"):
            eng.submit([1] * 4, max_new_tokens=1)
        assert eng.metrics.requests_submitted == 0  # raises never count

    def test_nonstrict_submit_rejects_structurally(self, tiny_llama):
        """Satellite: strict_submit=False turns validation failures into
        `rejected` terminal results so a server loop survives them."""
        eng = make_engine(tiny_llama, max_slots=1, max_seq=4, prefill_len=4,
                          strict_submit=False)
        bad = [eng.submit([]), eng.submit([1] * 5),
               eng.submit([1] * 4, max_new_tokens=1)]
        good = eng.submit([1, 2], max_new_tokens=1)
        results = eng.run()
        for rid in bad:
            assert results[rid].outcome == "rejected"
            assert results[rid].tokens == []
            assert results[rid].detail
        assert results[good].outcome == "ok"
        assert_conserved(eng)

    def test_pop_result_reclaims_terminal_record(self, tiny_llama):
        """A long-running serving loop pops each delivered result so the
        record map cannot grow for the server's lifetime."""
        eng = make_engine(tiny_llama, max_slots=1)
        rid = eng.submit([1, 2], max_new_tokens=2)
        assert eng.pop_result(rid) is None       # not yet terminal
        eng.run()
        popped = eng.pop_result(rid)
        assert popped is not None and popped.outcome == "ok"
        assert eng.result(rid) is None           # reclaimed
        assert eng.pop_result(rid) is None       # idempotent
        # metrics still conserve: pop only drops the record, not the count
        assert eng.metrics.requests_submitted == sum(
            eng.metrics.outcomes.values())

    def test_queue_capacity_sheds_oldest_first(self, tiny_llama):
        eng = make_engine(tiny_llama, max_slots=1, queue_capacity=2)
        ids = [eng.submit([1, 2], max_new_tokens=2) for _ in range(5)]
        results = eng.run()
        outcomes = [results[r].outcome for r in ids]
        # oldest queued requests shed; the freshest survive
        assert outcomes.count("shed") == 3
        assert outcomes[-1] == "ok" and outcomes[-2] == "ok"
        assert outcomes[:3] == ["shed"] * 3
        assert results[ids[0]].latency_s is not None
        assert_conserved(eng)

    def test_queued_deadline_times_out_before_admission(self, tiny_llama):
        eng = make_engine(tiny_llama, max_slots=1)
        stale = eng.submit([1, 2], max_new_tokens=2, ttl_s=1e-9)
        fresh = eng.submit([1, 2, 3], max_new_tokens=2)  # no deadline
        results = eng.run()
        assert results[stale].outcome == "timeout"
        assert results[stale].tokens == []
        assert "before admission" in results[stale].detail
        assert results[fresh].outcome == "ok"
        assert_conserved(eng)

    def test_default_ttl_applies_when_submit_omits_it(self, tiny_llama):
        eng = make_engine(tiny_llama, default_ttl_s=1e-9)
        rid = eng.submit([1, 2], max_new_tokens=2)
        override = eng.submit([1, 2, 3], max_new_tokens=2, ttl_s=0)  # opt out
        results = eng.run()
        assert results[rid].outcome == "timeout"
        assert results[override].outcome == "ok"
        assert_conserved(eng)


class TestQuarantine:
    def test_nan_quarantines_only_poisoned_slot(self, tiny_llama):
        """ISSUE 8 acceptance: injected NaN logits quarantine the
        poisoned slot; the OTHER slot's greedy output stays bit-identical
        to a fault-free run; decode compiled exactly once throughout."""
        cfg, params = tiny_llama

        def run_engine(injector):
            eng = make_engine(tiny_llama, injector=injector)
            a = eng.submit([1, 2, 3], max_new_tokens=8)
            b = eng.submit([7, 8, 9, 10], max_new_tokens=8)
            return eng, a, b, eng.run()

        _, a0, b0, clean = run_engine(None)
        inj = ServingFaultInjector(nan_logits_at_step=3, nan_logits_slot=0)
        eng, a1, b1, faulty = run_engine(inj)

        assert clean[a0].outcome == clean[b0].outcome == "ok"
        assert faulty[a1].outcome == "quarantined"
        assert faulty[a1].finish_reason == "quarantined"
        # prefill token + 2 decode tokens landed before the poisoned step
        assert faulty[a1].tokens == clean[a0].tokens[:3]
        assert "non-finite" in faulty[a1].detail
        # the neighbour slot never noticed
        assert faulty[b1].outcome == "ok"
        assert faulty[b1].tokens == clean[b0].tokens
        assert eng.decode_compile_count == 1
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert_conserved(eng)

    def test_slot_reuse_after_quarantine_is_clean(self, tiny_llama):
        """The quarantined slot's mutable pages are mask-cleared: the
        next occupant's output equals the plain forward's, and the decode
        step still never retraced."""
        cfg, params = tiny_llama
        inj = ServingFaultInjector(nan_logits_at_step=2, nan_logits_slot=0)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        poisoned = eng.submit([1, 2, 3], max_new_tokens=8)
        reused = eng.submit([9, 8, 7], max_new_tokens=4)
        results = eng.run()
        assert results[poisoned].outcome == "quarantined"
        assert results[reused].outcome == "ok"
        assert results[reused].tokens == greedy_by_forward(
            params, cfg, [9, 8, 7], 4)
        assert eng.decode_compile_count == 1
        assert_conserved(eng)

    def test_prefill_nonfinite_flag(self, tiny_llama):
        """Unit check of the in-step guard at prefill: a forward that
        NaNs one slot's logits flips exactly that slot's finite bit."""
        cfg, params = tiny_llama
        base = llama.forward_cached

        def poisoned_forward(params, tokens, cfg, cache, **kw):
            logits, new_cache = base(params, tokens, cfg, cache, **kw)
            bad = jnp.any(tokens == 63, axis=-1)  # magic poison token
            logits = jnp.where(bad[:, None, None], jnp.nan, logits)
            return logits, new_cache

        prefill = make_paged_prefill_step(
            cfg, GREEDY, page_size=4, seq_limit=16,
            forward_fn=poisoned_forward)
        from scaletorch_tpu.inference.kv_cache import init_paged_kv_cache
        # two slots x four pages of 4 tokens, page 0 the trash page
        pool = init_paged_kv_cache(cfg, 9, 4, dtype=jnp.float32)
        tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4)
        tokens = np.zeros((2, 8), np.int32)
        tokens[0, :3] = [1, 2, 63]      # poisoned prompt
        tokens[1, :3] = [1, 2, 3]
        _, _, finite, _ = prefill(
            params, jnp.asarray(tokens), jnp.asarray([3, 3], jnp.int32),
            jnp.zeros(2, jnp.int32), jnp.asarray([True, True]),
            jnp.asarray(tables), pool, jnp.zeros((2, 2), jnp.uint32),
        )
        assert list(np.asarray(finite)) == [False, True]

    def test_poison_request_quarantined_at_admission(self, tiny_llama):
        """End-to-end poison REQUEST: a prompt whose content NaNs the
        model is quarantined at admission (prefill), other requests are
        served normally."""
        cfg, params = tiny_llama
        base = llama.forward_cached

        def poisoned_forward(params, tokens, cfg, cache, **kw):
            logits, new_cache = base(params, tokens, cfg, cache, **kw)
            bad = jnp.any(tokens == 63, axis=-1)
            logits = jnp.where(bad[:, None, None], jnp.nan, logits)
            return logits, new_cache

        eng = make_engine(tiny_llama, forward_fn=poisoned_forward)
        poison = eng.submit([1, 2, 63], max_new_tokens=4)
        normal = eng.submit([7, 8, 9], max_new_tokens=4)
        results = eng.run()
        assert results[poison].outcome == "quarantined"
        assert results[poison].tokens == []
        assert "prefill" in results[poison].detail
        assert results[normal].outcome == "ok"
        assert results[normal].tokens == greedy_by_forward(
            params, cfg, [7, 8, 9], 4)
        assert_conserved(eng)


class TestStorms:
    def test_submit_storm_sheds(self, tiny_llama):
        """A burst beyond queue capacity sheds oldest-first with `shed`
        outcomes; the engine keeps serving."""
        inj = ServingFaultInjector(submit_storm_at_step=2,
                                   submit_storm_count=6)
        eng = make_engine(tiny_llama, max_slots=1, queue_capacity=2,
                          injector=inj)
        rid = eng.submit([1, 2, 3], max_new_tokens=6)
        results = eng.run()
        counts = Counter(r.outcome for r in results.values())
        assert results[rid].outcome == "ok"
        assert counts["shed"] == 4          # 6 injected, capacity 2 kept
        assert counts["ok"] == 1 + 2        # original + the 2 kept storms
        assert eng.metrics.requests_submitted == 7
        assert_conserved(eng)

    def test_deadline_storm_times_out_in_flight(self, tiny_llama):
        """A deadline storm expires queued AND mid-decode requests with
        `timeout` outcomes; partial tokens are kept; the engine survives
        and the metrics expose the deadline-miss rate."""
        inj = ServingFaultInjector(deadline_storm_at_step=3)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        active = eng.submit([1, 2, 3], max_new_tokens=20)
        queued = eng.submit([7, 8], max_new_tokens=2)
        results = eng.run()
        assert results[active].outcome == "timeout"
        assert "mid-decode" in results[active].detail
        assert len(results[active].tokens) == 3  # prefill + 2 decode steps
        assert results[queued].outcome == "timeout"
        assert "before admission" in results[queued].detail
        snap = eng.metrics.snapshot()
        assert snap["deadline_miss_rate"] == 1.0
        assert_conserved(eng)

    def test_post_storm_requests_serve_normally(self, tiny_llama):
        """After a deadline storm the engine must self-heal: later
        requests complete ok."""
        cfg, params = tiny_llama
        inj = ServingFaultInjector(deadline_storm_at_step=1)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        eng.submit([1, 2, 3], max_new_tokens=10)
        eng.run()
        rid = eng.submit([4, 5, 6], max_new_tokens=4)
        results = eng.run()
        assert results[rid].outcome == "ok"
        assert results[rid].tokens == greedy_by_forward(
            params, cfg, [4, 5, 6], 4)
        assert eng.decode_compile_count == 1
        assert_conserved(eng)


class TestDrain:
    def test_drain_finishes_in_flight_and_stops_admissions(self, tiny_llama):
        eng = make_engine(tiny_llama, max_slots=1)
        admitted = eng.submit([1, 2, 3], max_new_tokens=4)
        queued = eng.submit([7, 8], max_new_tokens=2)
        eng.step()                        # admit the first request
        results = eng.drain()
        assert results[admitted].outcome == "ok"
        assert len(results[admitted].tokens) == 4
        assert results[queued].outcome == "aborted"   # never admitted
        assert eng.draining
        with pytest.raises(EngineDraining):
            eng.submit([1, 2], max_new_tokens=1)
        assert_conserved(eng)

    def test_drain_finish_queued_serves_everything(self, tiny_llama):
        eng = make_engine(tiny_llama, max_slots=1)
        ids = [eng.submit([1, 2], max_new_tokens=2) for _ in range(3)]
        results = eng.drain(finish_queued=True)
        assert all(results[r].outcome == "ok" for r in ids)
        assert_conserved(eng)

    def test_drain_nonstrict_submit_rejects(self, tiny_llama):
        eng = make_engine(tiny_llama, strict_submit=False)
        eng.drain()
        rid = eng.submit([1, 2], max_new_tokens=1)
        res = eng.result(rid)
        assert res.outcome == "rejected"
        assert "draining" in res.detail
        assert_conserved(eng)

    def test_sigterm_drains_and_returns_cleanly(self, tiny_llama):
        """ISSUE 8 acceptance: drain() under SIGTERM finishes in-flight
        requests and run() returns cleanly — the existing
        PreemptionHandler SIGTERM path, not a new signal stack."""
        handler = PreemptionHandler()
        eng = make_engine(tiny_llama, max_slots=1, preemption=handler)
        admitted = eng.submit([1, 2, 3], max_new_tokens=6)
        queued = eng.submit([7, 8], max_new_tokens=30)
        eng.step()                                   # admit request 0
        handler.trigger(signal.SIGTERM)              # simulated delivery
        results = eng.run()
        assert results[admitted].outcome == "ok"
        assert len(results[admitted].tokens) == 6    # finished, not cut
        assert results[queued].outcome == "aborted"
        assert eng.draining
        assert eng.decode_compile_count == 1
        assert_conserved(eng)


class TestWatchdog:
    def test_slow_decode_fires_serving_watchdog(self, tiny_llama, tmp_path):
        """A stalled step() fires the serving watchdog: crash report with
        the engine metrics snapshot (outcome counters included) and exit
        code 44 — with an injected exit_fn recorder standing in for
        os._exit."""
        exits = []
        inj = ServingFaultInjector(slow_decode_at_step=2,
                                   slow_decode_seconds=0.6)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        wd = make_serving_watchdog(
            eng, timeout=0.15, crash_report_dir=str(tmp_path),
            exit_fn=exits.append)
        assert eng.watchdog is wd
        rid = eng.submit([1, 2, 3], max_new_tokens=4)
        with wd:
            results = eng.run()
        assert exits == [SERVING_STALL_EXIT_CODE]
        assert wd.fired
        # the injected exit_fn does not kill the process, so the stalled
        # step completes and the request still lands
        assert results[rid].outcome == "ok"
        reports = [f for f in os.listdir(tmp_path)
                   if f.startswith("crash_report")]
        assert len(reports) == 1
        with open(tmp_path / reports[0]) as f:
            report = json.load(f)
        assert report["serving"] is True
        assert report["exit_code"] == SERVING_STALL_EXIT_CODE
        assert "requests_quarantined" in report["counters"]
        assert "thread_stacks" in report

    def test_healthy_run_never_fires(self, tiny_llama):
        exits = []
        eng = make_engine(tiny_llama, max_slots=1)
        wd = make_serving_watchdog(eng, timeout=30.0, exit_fn=exits.append)
        rid = eng.submit([1, 2], max_new_tokens=2)
        with wd:
            results = eng.run()
        assert not wd.fired and exits == []
        assert results[rid].outcome == "ok"


class TestConservationProperty:
    @pytest.mark.parametrize("seed", range(4))
    def test_randomized_schedule_conserves_requests(self, tiny_llama, seed):
        """Property-style drill: a randomized submit/fault schedule
        (valid, over-long and empty prompts; random TTLs; a random NaN
        poke; bounded queue) always satisfies
        submitted == ok+timeout+shed+rejected+quarantined+aborted, leaves
        no slot active, and never retraces the decode step."""
        rng = random.Random(seed)
        inj = ServingFaultInjector(
            nan_logits_at_step=rng.randint(2, 5),
            nan_logits_slot=rng.randint(0, 1),
            deadline_storm_at_step=(
                rng.randint(4, 8) if rng.random() < 0.5 else 0),
        )
        eng = make_engine(
            tiny_llama, max_slots=2, queue_capacity=rng.randint(1, 3),
            strict_submit=False, injector=inj,
        )
        # one long-lived anchor request guarantees decode steps happen
        eng.submit([1, 2], max_new_tokens=rng.randint(6, 12))
        for _ in range(rng.randint(4, 10)):
            kind = rng.random()
            if kind < 0.15:
                eng.submit([])                            # rejected
            elif kind < 0.3:
                eng.submit([1] * 20)                      # rejected
            else:
                eng.submit(
                    [rng.randint(1, 62)
                     for _ in range(rng.randint(1, 6))],
                    max_new_tokens=rng.randint(1, 8),
                    ttl_s=rng.choice([None, None, 1e-9, 5.0]),
                )
            if rng.random() < 0.3:
                eng.step()
        results = eng.run(max_steps=rng.choice([5, 100]))
        assert_conserved(eng)
        assert eng.metrics.decode_steps > 0
        assert eng.decode_compile_count == 1
        counts = Counter(r.outcome for r in results.values())
        snap = eng.metrics.snapshot()
        for outcome in TERMINAL_OUTCOMES:
            assert snap[f"requests_{outcome}"] == counts.get(outcome, 0)

    def test_snapshot_rates(self, tiny_llama):
        """Satellite: the per-outcome counters plus deadline-miss and
        quarantine rates ride snapshot() (and therefore the monitor ring
        buffer + crash reports)."""
        inj = ServingFaultInjector(nan_logits_at_step=2, nan_logits_slot=0)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        eng.submit([1, 2, 3], max_new_tokens=8)
        eng.submit([4, 5], max_new_tokens=1, ttl_s=1e-9)
        eng.run()
        snap = eng.metrics.snapshot()
        assert snap["requests_quarantined"] == 1
        assert snap["requests_timeout"] == 1
        assert snap["quarantine_rate"] == 0.5
        assert snap["deadline_miss_rate"] == 0.5

    def test_outcome_counters_ride_monitor_ring_buffer(self, tiny_llama):
        pytest.importorskip("psutil")
        from scaletorch_tpu.utils.monitor import SystemMonitor

        mon = SystemMonitor(max_records=16)
        eng = make_engine(tiny_llama, max_slots=1, monitor=mon,
                          monitor_every=1)
        eng.submit([1, 2], max_new_tokens=3)
        eng.run()
        assert mon.records
        last = mon.records[-1]
        assert "requests_ok" in last
        assert "deadline_miss_rate" in last


class TestTimingFields:
    def test_partial_results_keep_ttft_and_latency(self, tiny_llama):
        inj = ServingFaultInjector(deadline_storm_at_step=2)
        eng = make_engine(tiny_llama, max_slots=1, injector=inj)
        rid = eng.submit([1, 2, 3], max_new_tokens=20)
        results = eng.run()
        res = results[rid]
        assert res.outcome == "timeout"
        assert res.ttft_s is not None and res.ttft_s >= 0
        assert res.latency_s is not None and res.latency_s >= res.ttft_s

    def test_never_started_results_have_no_ttft(self, tiny_llama):
        eng = make_engine(tiny_llama, max_slots=1)
        rid = eng.submit([1, 2], max_new_tokens=2, ttl_s=1e-9)
        results = eng.run()
        assert results[rid].outcome == "timeout"
        assert results[rid].ttft_s is None
        assert results[rid].latency_s is not None


class TestInjectorConfig:
    def test_from_config_env_parity(self, monkeypatch):
        class Cfg:
            ft_serve_nan_at_step = 3
            ft_serve_nan_slot = 1
            ft_serve_slow_at_step = 0
            ft_serve_slow_seconds = 2.5
            ft_serve_submit_storm_at_step = 7
            ft_serve_submit_storm_count = 4
            ft_serve_deadline_storm_at_step = 0

        inj = ServingFaultInjector.from_config(Cfg())
        assert inj.nan_logits_at_step == 3
        assert inj.nan_logits_slot == 1
        assert inj.submit_storm_at_step == 7
        assert inj.submit_storm_count == 4
        assert inj.slow_decode_seconds == 2.5
        assert inj.active

        # present-wins: an explicit env 0 CANCELS a config-armed drill
        monkeypatch.setenv("SCALETORCH_TPU_FT_SERVE_NAN_STEP", "0")
        monkeypatch.setenv("SCALETORCH_TPU_FT_SERVE_SUBMIT_STORM_STEP", "0")
        monkeypatch.setenv("SCALETORCH_TPU_FT_SERVE_DEADLINE_STORM_STEP", "9")
        inj = ServingFaultInjector.from_config(Cfg())
        assert inj.nan_logits_at_step == 0
        assert inj.submit_storm_at_step == 0
        assert inj.deadline_storm_at_step == 9

    def test_cli_flags_parse(self):
        from scaletorch_tpu.config import parse_args

        cfg = parse_args([
            "--ft_serve_nan_at_step", "5",
            "--ft_serve_submit_storm_at_step", "2",
            "--ft_serve_submit_storm_count", "16",
        ])
        inj = ServingFaultInjector.from_config(cfg)
        assert inj.nan_logits_at_step == 5
        assert inj.submit_storm_at_step == 2
        assert inj.submit_storm_count == 16

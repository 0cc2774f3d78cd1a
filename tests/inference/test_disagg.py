"""Disaggregated prefill/decode engine (ISSUE 19): MPMD phase slices
with page-ownership handoff.

The colocated paged engine is the standing parity oracle — greedy
outputs must be BIT-IDENTICAL across the split for llama-GQA and qwen3
schedules, with the one-compile discipline on BOTH slice programs
(``prefill_compile_count == 1`` and ``decode_compile_count == 1``
through admissions, handoffs, quarantines and transport faults).
Conservation is the other oracle: both pools' ``check_conservation``
stay green under randomized admit/handoff/crash-mid-handoff/cancel/
drain schedules, and every request ends in exactly ONE of the six
terminal outcomes. Quick tier, CPU (8 virtual devices via conftest).
"""

import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference import (
    DisaggregatedEngine,
    InferenceEngine,
    PageHandoffChannel,
    SamplingParams,
)
from scaletorch_tpu.inference.disagg import (
    parse_disagg_spec,
    plan_slice_split,
)
from scaletorch_tpu.models import llama, qwen3

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)
SCHEDULE = [([1, 2, 3], 3), ([9, 8], 5), ([4, 5, 6, 7], 2), ([11], 6),
            ([1, 2, 3, 5], 4)]
OUTCOMES = {"ok", "timeout", "aborted", "quarantined", "rejected", "shed"}


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def make_colocated(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", 4)
    return InferenceEngine(params, cfg, **kw)


def make_disagg(params, cfg, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("sampling", GREEDY)
    kw.setdefault("page_size", 4)
    kw.setdefault("disagg_split", (4, 4))
    return DisaggregatedEngine(params, cfg, **kw)


def serve(eng, schedule=SCHEDULE):
    ids = [eng.submit(p, max_new_tokens=n) for p, n in schedule]
    results = eng.run()
    return [results[i] for i in ids]


def poisoned(cfg):
    """Forward whose logits NaN whenever the magic token 63 appears —
    the poison-REQUEST drill from the resilience suite."""
    base = llama.forward_cached

    def forward(params, tokens, cfg, cache, *, positions,
                write_mask=None, **kw):
        logits, new_cache = base(params, tokens, cfg, cache,
                                 positions=positions,
                                 write_mask=write_mask, **kw)
        bad = jnp.any(tokens == 63, axis=-1)
        return jnp.where(bad[:, None, None], jnp.nan, logits), new_cache

    return forward


def assert_conserved_both(eng):
    """After a drain, NEITHER pool leaked: conservation green on both
    allocators, and evicting the decode-side radix returns BOTH pools
    to full capacity (the prefill pool holds nothing across ticks)."""
    eng.check_conservation()
    assert all(not s.active for s in eng._slots)
    assert not eng._handoff
    if eng.radix is not None:
        eng.radix.evict(eng.num_pages)
    assert eng.allocator.free_count == eng.allocator.capacity
    assert (eng.prefill_allocator.free_count
            == eng.prefill_allocator.capacity)


class TestDisaggParity:
    """Acceptance: disagg greedy outputs == colocated, both compile
    counts == 1, conservation green after drain."""

    def _check(self, cfg, params, **kw):
        colo = serve(make_colocated(params, cfg))
        eng = make_disagg(params, cfg, **kw)
        dis = serve(eng)
        for c, d in zip(colo, dis):
            assert d.tokens == c.tokens
            assert d.finish_reason == c.finish_reason
            assert d.outcome == "ok"
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert eng.decode_compile_count == 1
        assert eng.metrics.handoffs > 0
        assert_conserved_both(eng)
        return eng

    def test_llama_gqa(self, tiny_llama):
        self._check(*tiny_llama)

    def test_qwen3(self):
        cfg = qwen3.Qwen3Config(**{**TINY, "head_dim": 16})
        self._check(cfg, qwen3.init_params(jax.random.PRNGKey(0), cfg))

    def test_prefix_cache_off_still_identical(self, tiny_llama):
        cfg, params = tiny_llama
        eng = self._check(cfg, params, prefix_cache=False)
        assert eng.radix is None

    def test_auto_split_follows_budget_plan(self, tiny_llama):
        """disagg_split=None sizes the slices from the CI-attested HBM
        budget rows — on the 8-virtual-device mesh that must equal
        plan_slice_split's answer, and parity must hold on it too."""
        cfg, params = tiny_llama
        n_p, n_d = plan_slice_split(len(jax.devices()))
        eng = self._check(cfg, params, disagg_split=None)
        assert eng.metrics.prefill_slice_devices == n_p
        assert eng.metrics.decode_slice_devices == n_d

    def test_quarantine_drill_matches_colocated(self, tiny_llama):
        """A poison prompt quarantines at the PREFILL slice (tokens [],
        prefill-pool lines cleared + released); its neighbour's output
        stays bit-identical to the colocated engine under the same
        drill, with zero retraces on either slice program."""
        cfg, params = tiny_llama
        schedule = [([1, 2, 63], 4), ([7, 8, 9], 4)]
        colo = serve(
            make_colocated(params, cfg, forward_fn=poisoned(cfg)),
            schedule)
        eng = make_disagg(params, cfg, forward_fn=poisoned(cfg))
        dis = serve(eng, schedule)
        for c, d in zip(colo, dis):
            assert d.outcome == c.outcome
            assert d.tokens == c.tokens
        assert dis[0].outcome == "quarantined"
        assert dis[0].tokens == []
        assert "prefill" in dis[0].detail
        assert dis[1].outcome == "ok"
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert eng.decode_compile_count == 1
        assert_conserved_both(eng)


class TestHandoffProperties:
    def test_counters_and_channel_agree(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg)
        serve(eng)
        m = eng.metrics
        assert m.handoffs == eng.channel.transfers
        assert m.pages_handed_off == eng.channel.pages_transferred > 0
        assert m.handoff_bytes == eng.channel.bytes_transferred > 0
        assert m.hist["handoff"].count == m.handoffs
        snap = m.snapshot()
        for key in ("prefill_slice_devices", "decode_slice_devices",
                    "handoffs", "pages_handed_off", "handoff_bytes",
                    "prefill_slice_busy_fraction",
                    "decode_slice_busy_fraction", "prefill_pool_free"):
            assert key in snap, key
        busy_p, busy_d = m.busy_fractions()
        assert 0.0 < busy_p <= 1.0
        assert 0.0 < busy_d <= 1.0

    def test_prefix_sharing_transfers_fewer_pages(self, tiny_llama):
        """The decode-side radix keeps handed-off prompt pages frozen:
        a second request with the same page-aligned prefix retains the
        shared pages on the decode pool and only the tail page crosses
        the wire — the hit saves TRANSFER, visible in the channel."""
        cfg, params = tiny_llama
        sys_prompt = [7, 7, 7, 7, 3, 3, 3, 3]  # two full pages
        eng = make_disagg(params, cfg, prefill_len=12)
        r1 = eng.submit(sys_prompt + [1], max_new_tokens=4)
        eng.run()
        first_pages = eng.channel.pages_transferred
        assert first_pages == 3  # ceil(9 / 4)
        r2 = eng.submit(sys_prompt + [2], max_new_tokens=4)
        results = eng.run()
        assert eng.channel.pages_transferred - first_pages == 1
        assert eng.metrics.prefix_hits == 1
        # disagg always prefills the full prompt — the hit must NOT
        # claim saved prefill tokens
        assert eng.metrics.prefill_tokens_saved == 0
        ref = make_colocated(params, cfg, prefill_len=12)
        rr = ref.submit(sys_prompt + [2], max_new_tokens=4)
        assert results[r2].tokens == ref.run()[rr].tokens
        assert results[r1].tokens is not None
        assert eng.decode_compile_count == 1
        assert_conserved_both(eng)

    def test_stop_at_first_token_skips_handoff(self, tiny_llama):
        """max_new_tokens=1 finishes at the prefill slice: one token,
        reason 'length', zero handoffs, prefill pages released."""
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg)
        res = serve(eng, [([1, 2, 3], 1)])[0]
        assert res.outcome == "ok"
        assert res.finish_reason == "length"
        assert len(res.tokens) == 1
        ref = serve(make_colocated(params, cfg), [([1, 2, 3], 1)])[0]
        assert res.tokens == ref.tokens
        assert eng.metrics.handoffs == 0
        assert eng.channel.transfers == 0
        assert eng.decode_compile_count == 0  # decode slice never ran
        assert_conserved_both(eng)


class TestMidHandoffDeath:
    def test_transport_fault_aborts_exactly_once(self, tiny_llama):
        """An injected wire fault on the FIRST transfer: that request
        ends aborted (its streamed first token attached), the decode-
        side reservation rolls back whole, the NEXT request hands off
        normally with bit-identical tokens — one terminal, zero leaks,
        zero retraces."""
        cfg, params = tiny_llama
        channel = PageHandoffChannel()
        channel.fail_next()
        eng = make_disagg(params, cfg, channel=channel)
        schedule = [([1, 2, 3], 5), ([7, 8, 9], 5)]
        aborted, ok = serve(eng, schedule)
        assert aborted.outcome == "aborted"
        assert "handoff failed" in aborted.detail
        assert len(aborted.tokens) == 1  # the already-streamed token
        assert ok.outcome == "ok"
        ref = serve(make_colocated(params, cfg), [([7, 8, 9], 5)])[0]
        assert ok.tokens == ref.tokens
        assert eng.metrics.handoff_failures == 1
        assert channel.failures == 1
        assert eng.metrics.handoffs == 1
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert eng.decode_compile_count == 1
        assert_conserved_both(eng)

    def test_deadline_expires_awaiting_handoff(self, tiny_llama):
        """A prefilled request whose deadline passes while it queues for
        a decode slot ends as exactly one `timeout` — prefill pages
        released, the occupant request unaffected."""
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg, max_slots=1)
        occupant = eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()  # occupant prefilled + bound to the only decode slot
        blocked = eng.submit([4, 5, 6], max_new_tokens=5, ttl_s=0.15)
        eng.step()  # blocked prefills, waits in the handoff queue
        assert len(eng._handoff) == 1
        time.sleep(0.2)
        eng.step()  # deadline sweep drops it
        results = eng.run()
        assert results[blocked].outcome == "timeout"
        assert "handoff" in results[blocked].detail
        assert len(results[blocked].tokens) == 1
        assert results[occupant].outcome == "ok"
        assert eng.decode_compile_count == 1
        assert_conserved_both(eng)

    def test_cancel_in_handoff_queue(self, tiny_llama):
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg, max_slots=1)
        occupant = eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()
        blocked = eng.submit([4, 5, 6], max_new_tokens=5)
        eng.step()
        assert len(eng._handoff) == 1
        assert eng.cancel(blocked) is True
        assert not eng._handoff
        results = eng.run()
        assert results[blocked].outcome == "aborted"
        assert results[occupant].outcome == "ok"
        assert_conserved_both(eng)

    def test_drain_finishes_handoff_queue(self, tiny_llama):
        """A prefilled request parked in the handoff queue is IN-FLIGHT
        (its first token already streamed): a graceful drain completes
        it through the decode slice, bit-identical — it is not part of
        the never-admitted backlog drain aborts."""
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg, max_slots=1)
        eng.submit([1, 2, 3], max_new_tokens=20)
        eng.step()
        blocked = eng.submit([4, 5, 6], max_new_tokens=5)
        eng.step()
        assert len(eng._handoff) == 1
        results = eng.drain()
        assert results[blocked].outcome == "ok"
        ref = serve(make_colocated(params, cfg, max_slots=1),
                    [([4, 5, 6], 5)])[0]
        assert results[blocked].tokens == ref.tokens
        assert_conserved_both(eng)


class TestRandomizedConservation:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_schedule_conserves_both_pools(self, tiny_llama, seed):
        """Randomized interleavings of submit (incl. poison prompts and
        near-expired deadlines), cancel, injected wire faults, and ticks
        — then a full drain. Oracle: submitted == sum(outcomes), every
        outcome one of the six terminals, conservation green on BOTH
        pools, the radix evictable back to full capacity, and at most
        one compile per slice program through it all."""
        cfg, params = tiny_llama
        channel = PageHandoffChannel()
        eng = make_disagg(params, cfg, channel=channel,
                          forward_fn=poisoned(cfg), strict_submit=False)
        rng = random.Random(seed)
        ids = []
        for _ in range(40):
            op = rng.random()
            if op < 0.5:
                prompt = [rng.randint(1, 62)
                          for _ in range(rng.randint(1, 8))]
                if rng.random() < 0.15:
                    prompt[-1] = 63  # poison -> quarantined at prefill
                kw = {}
                if rng.random() < 0.15:
                    kw["ttl_s"] = 0.001  # -> timeout somewhere en route
                ids.append(eng.submit(
                    prompt, max_new_tokens=rng.randint(1, 6), **kw))
            elif op < 0.62 and ids:
                eng.cancel(rng.choice(ids))
            elif op < 0.72:
                channel.fail_next()  # next handoff dies mid-wire
            else:
                eng.step()
        results = eng.run()
        assert len(ids) == eng.metrics.requests_submitted
        assert all(i in results for i in ids)
        assert sum(eng.metrics.outcomes.values()) == len(ids)
        assert set(eng.metrics.outcomes) <= OUTCOMES
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        assert eng.decode_compile_count <= 1
        assert_conserved_both(eng)


class TestPlanningAndValidation:
    def test_parse_disagg_spec(self):
        assert parse_disagg_spec("4:4") == (4, 4)
        assert parse_disagg_spec(" 3:5 ") == (3, 5)
        assert parse_disagg_spec("") is None
        assert parse_disagg_spec("auto") is None
        assert parse_disagg_spec("none") is None
        for bad in ("4", "1:2:3", "a:b", "4:"):
            with pytest.raises(ValueError, match="disagg spec"):
                parse_disagg_spec(bad)
        with pytest.raises(ValueError, match=">= 1 device"):
            parse_disagg_spec("0:4")

    def test_plan_slice_split_reads_budget(self, tmp_path):
        budget = tmp_path / "hbm.json"
        budget.write_text(
            '{"entries": {"disagg_prefill_slice": {"peak_mb": 3.0}, '
            '"disagg_decode_slice": {"peak_mb": 1.0}}}')
        assert plan_slice_split(8, budget_path=str(budget)) == (6, 2)
        # unreadable budget degrades to an even split, never an error
        assert plan_slice_split(
            8, budget_path=str(tmp_path / "missing.json")) == (4, 4)
        # each slice always keeps at least one device
        assert plan_slice_split(2, budget_path=str(budget)) == (1, 1)
        with pytest.raises(ValueError, match=">= 2 devices"):
            plan_slice_split(1)

    def test_checked_in_budget_covers_the_mesh(self):
        """The real tools/hbm_budget.json rows must plan a valid split
        for the CI mesh (the sizing recipe the docs name)."""
        n_p, n_d = plan_slice_split(len(jax.devices()))
        assert n_p >= 1 and n_d >= 1
        assert n_p + n_d == len(jax.devices())

    def test_constructor_validation(self, tiny_llama):
        cfg, params = tiny_llama
        with pytest.raises(ValueError, match="slice meshes"):
            make_disagg(params, cfg, mesh=object())
        with pytest.raises(ValueError, match="devices"):
            make_disagg(params, cfg, disagg_split=(8, 8))
        with pytest.raises(ValueError, match=">= 2 devices"):
            make_disagg(params, cfg, devices=[jax.devices()[0]],
                        disagg_split=None)
        with pytest.raises(ValueError, match="prefill_pool_pages"):
            make_disagg(params, cfg, prefill_pool_pages=1)

    def test_slice_placement_is_disjoint(self, tiny_llama):
        """MPMD, attested on devices: the decode pool lives ONLY on
        decode-slice devices, the prefill pool + param copy ONLY on
        prefill-slice devices."""
        cfg, params = tiny_llama
        eng = make_disagg(params, cfg)
        prefill_devs = set(eng.prefill_mesh.devices.flat)
        decode_devs = set(eng.decode_mesh.devices.flat)
        assert not (prefill_devs & decode_devs)
        assert set(eng.cache.k.sharding.device_set) == decode_devs
        assert set(eng.prefill_cache.k.sharding.device_set) \
            == prefill_devs
        leaf = jax.tree.leaves(eng._params_prefill)[0]
        assert set(leaf.sharding.device_set) == prefill_devs


class TestDisaggTelemetry:
    def test_jsonl_export_carries_disagg_kind(self, tiny_llama, tmp_path):
        from scaletorch_tpu.telemetry.export import (
            KNOWN_KINDS,
            TelemetryExporter,
            read_jsonl,
        )

        assert "disagg" in KNOWN_KINDS
        cfg, params = tiny_llama
        path = str(tmp_path / "events.jsonl")
        exporter = TelemetryExporter(path)
        eng = make_disagg(params, cfg, exporter=exporter)
        serve(eng, [([1, 2, 3], 4)])
        exporter.close()
        records = read_jsonl(path)
        kinds = {r["kind"] for r in records}
        assert {"engine_metrics", "disagg"} <= kinds
        dis = [r for r in records if r["kind"] == "disagg"][-1]
        assert dis["prefill_slice_devices"] == 4
        assert dis["decode_slice_devices"] == 4
        assert dis["handoffs"] >= 1
        assert dis["pages_handed_off"] >= 1
        assert dis["handoff_failures"] == 0

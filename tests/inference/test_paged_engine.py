"""The engine on its page pool: greedy outputs equal to the plain
forward's (tests/inference/oracle.py: no cache, none of the engine's
code) across the five served families, plain and staggered admissions
and prefix hits (including a PR 7 quarantine drill), one-compile
discipline through admissions + prefix hits + quarantine clears +
frees, counter-attested prefix reuse, page-budget admission,
conservation, and TP-sharded serving on the virtual mesh. Quick tier,
CPU.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from scaletorch_tpu.inference import (
    InferenceEngine,
    SamplingParams,
    ServingFaultInjector,
)
from scaletorch_tpu.models import gpt_moe, llama, qwen3, qwen3_moe
from tests.inference.oracle import assert_greedy, greedy_by_forward

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)
GREEDY = SamplingParams(temperature=0.0)

SCHEDULE = [([1, 2, 3], 3), ([9, 8], 5), ([4, 5, 6, 7], 2), ([11], 6),
            ([1, 2, 3, 5], 4)]


@pytest.fixture(scope="module")
def tiny_llama():
    cfg = llama.LlamaConfig(**TINY)
    return cfg, llama.init_params(jax.random.PRNGKey(0), cfg)


def serve(params, cfg, schedule=SCHEDULE, *, injector=None,
          prefill_len=8, page_size=4, max_slots=2, **kw):
    eng = InferenceEngine(
        params, cfg, max_slots=max_slots, max_seq=32,
        prefill_len=prefill_len, sampling=GREEDY, page_size=page_size,
        injector=injector, **kw)
    ids = [eng.submit(p, max_new_tokens=n) for p, n in schedule]
    results = eng.run()
    return eng, [results[i] for i in ids]


def assert_pages_conserved(eng):
    """After a full drain every page reference left belongs to the radix
    tree; evicting it all returns the pool to capacity."""
    eng.allocator.check_conservation()
    assert all(not s.active for s in eng._slots)
    if eng.radix is not None:
        eng.radix.evict(eng.num_pages)
    assert eng.allocator.free_count == eng.allocator.capacity


def assert_oracle(params, cfg, schedule, results):
    for (prompt, n), r in zip(schedule, results):
        assert r.outcome == "ok" and r.finish_reason == "length"
        assert len(r.tokens) == n
        assert_greedy(params, cfg, prompt, r.tokens)


def family(name):
    """(cfg, params) of one served family at a tiny size. The MoE
    families drop no token (dropless routing, or a capacity that holds
    every row), so a request's tokens do not depend on its batch."""
    key = jax.random.PRNGKey(0)
    if name == "llama":       # GQA: 4 query heads over 2 KV heads
        cfg = llama.LlamaConfig(**TINY)
        return cfg, llama.init_params(key, cfg)
    if name == "qwen3":       # qk-norm, tied embeddings
        cfg = qwen3.Qwen3Config(**{**TINY, "head_dim": 16})
        return cfg, qwen3.init_params(key, cfg)
    if name == "qwen3_moe":
        cfg = qwen3_moe.Qwen3MoEConfig(
            **{**TINY, "head_dim": 16}, moe_intermediate_size=48,
            num_experts=4, num_experts_per_tok=2, dropless=True,
            tie_word_embeddings=False)
        return cfg, qwen3_moe.init_params(key, cfg)
    if name == "olmoe":
        from tests.models.test_olmoe import seeded_params, tiny_config

        cfg = tiny_config()
        return cfg, seeded_params(cfg)
    if name == "gpt_moe":
        cfg = gpt_moe.GPTMoEConfig(
            block_size=32, vocab_size=64, n_layer=2, n_head=4, n_embd=32,
            num_experts=4, top_k=2, capacity_factor=2.0)
        return cfg, gpt_moe.init_params(key, cfg)
    raise KeyError(name)


SYSTEM = [7, 7, 7, 7, 3, 3, 3, 3]  # two full pages at page_size=4
SCENARIOS = {
    # both requests admitted by one prefill call, decoded side by side
    "plain": [([1, 2, 3], 6), ([7, 8, 9, 10, 11], 6)],
    # five requests over two slots: each later one is admitted into a
    # slot freed by a retirement while the other slot is mid-decode
    "staggered": SCHEDULE,
    # the second and third share the first's two system-prompt pages
    "prefix_hit": [(SYSTEM + [1], 4), (SYSTEM + [2], 4),
                   (SYSTEM + [5, 6], 3)],
}


@pytest.mark.parametrize("scenario", list(SCENARIOS))
@pytest.mark.parametrize(
    "name", ["llama", "qwen3", "qwen3_moe", "olmoe", "gpt_moe"])
def test_engine_tokens_equal_the_plain_forwards(name, scenario):
    """The oracle shares no code with the engine: the plain forward on
    the whole sequence each step, argmax (tests/inference/oracle.py)."""
    cfg, params = family(name)
    schedule = SCENARIOS[scenario]
    if scenario == "prefix_hit":
        # one slot: the first request is retired, and its prompt pages
        # registered, before the next is admitted
        eng, results = serve(params, cfg, schedule[:1], prefill_len=12,
                             max_slots=1)
        ids = [eng.submit(p, max_new_tokens=n) for p, n in schedule[1:]]
        done = eng.run()
        results += [done[i] for i in ids]
        assert eng.metrics.prefix_hits == 2
        assert eng.metrics.prefill_tokens_saved == 2 * len(SYSTEM)
    else:
        eng, results = serve(params, cfg, schedule)
        admissions = 1 if scenario == "plain" else 3
        assert eng.metrics.prefill_calls >= admissions
    assert_oracle(params, cfg, schedule, results)
    assert eng.decode_compile_count == 1
    assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
    assert_pages_conserved(eng)


def test_engine_without_a_layout_argument_serves_from_the_pool(tiny_llama):
    """There is one layout: an engine that was told nothing about its
    cache pages it, and the option that chose is gone."""
    cfg, params = tiny_llama
    eng = InferenceEngine(params, cfg, sampling=GREEDY)
    assert eng.cache.k.shape == (
        cfg.num_hidden_layers, eng.num_pages, cfg.num_key_value_heads,
        eng.page_size, cfg.actual_head_dim)
    assert eng.num_pages == eng.max_slots * eng._pages_per_slot + 1
    rid = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.step()
    assert eng.metrics.pages_in_use > 0
    assert eng.run()[rid].tokens == greedy_by_forward(
        params, cfg, [1, 2, 3], 4)
    assert_pages_conserved(eng)
    for gone in ("cache_layout", "batch_axis"):
        with pytest.raises(TypeError, match=gone):
            InferenceEngine(params, cfg, **{gone: "paged"})


class TestEngineMatchesPlainForward:
    def _check(self, cfg, params, page_size=4):
        ep, results = serve(params, cfg, page_size=page_size)
        assert_oracle(params, cfg, SCHEDULE, results)
        assert ep.decode_compile_count == 1
        assert 1 <= ep.prefill_compile_count <= len(ep.prefill_shapes)
        assert_pages_conserved(ep)

    def test_llama_gqa(self, tiny_llama):
        self._check(*tiny_llama)

    def test_llama_page_size_misaligned_with_seq(self, tiny_llama):
        cfg, params = tiny_llama
        self._check(cfg, params, page_size=5)  # max_seq % page_size != 0

    def test_qwen3(self):
        self._check(*family("qwen3"))

    def test_qwen3_moe(self):
        cfg = qwen3_moe.Qwen3MoEConfig(
            **{**TINY, "head_dim": 16}, moe_intermediate_size=48,
            num_experts=4, num_experts_per_tok=2, capacity_factor=2.0,
            tie_word_embeddings=False,
        )
        self._check(cfg, qwen3_moe.init_params(jax.random.PRNGKey(0), cfg))

    def test_quarantine_drill_bit_identity(self, tiny_llama):
        """PR 7 drill: a poisoned slot quarantines, what it emitted
        before the fault and its NEIGHBOUR's whole output stay the
        fault-free run's and the plain forward's, and nothing retraces
        through the page-clear."""
        cfg, params = tiny_llama
        schedule = [([1, 2, 3], 8), ([7, 8, 9, 10], 8)]
        _, clean = serve(params, cfg, schedule)
        assert_oracle(params, cfg, schedule, clean)
        ep, paged = serve(
            params, cfg, schedule,
            injector=ServingFaultInjector(nan_logits_at_step=3,
                                          nan_logits_slot=0))
        assert paged[0].outcome == "quarantined"
        assert 0 < len(paged[0].tokens) < 8
        assert paged[0].tokens == clean[0].tokens[: len(paged[0].tokens)]
        assert paged[1].outcome == "ok"
        assert paged[1].tokens == clean[1].tokens  # neighbour unaffected
        assert ep.decode_compile_count == 1
        assert 1 <= ep.prefill_compile_count <= len(ep.prefill_shapes)
        assert_pages_conserved(ep)

    def test_slot_reuse_after_quarantine_is_clean(self, tiny_llama):
        """The quarantined request's mutable pages are cleared and
        released; the next occupant of the pool sees none of them."""
        cfg, params = tiny_llama
        inj = ServingFaultInjector(nan_logits_at_step=2, nan_logits_slot=0)
        eng = InferenceEngine(params, cfg, max_slots=1, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4,
                              injector=inj)
        poisoned = eng.submit([1, 2, 3], max_new_tokens=8)
        reused = eng.submit([9, 8, 7], max_new_tokens=4)
        results = eng.run()
        assert results[poisoned].outcome == "quarantined"
        assert results[reused].outcome == "ok"
        assert results[reused].tokens == greedy_by_forward(
            params, cfg, [9, 8, 7], 4)
        assert eng.decode_compile_count == 1
        assert_pages_conserved(eng)


class TestPrefixSharing:
    SYS = [7, 7, 7, 7, 3, 3, 3, 3]  # two full pages at page_size=4

    def test_second_request_reuses_prefix_pages(self, tiny_llama):
        """Counter-attested reuse: the second request with the shared
        system prompt prefills ZERO forward tokens for the shared pages
        (prefill_tokens_saved == shared length), physically shares the
        first request's frozen pages, and its output is the plain
        forward's, which shares nothing."""
        cfg, params = tiny_llama
        eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                              prefill_len=12, sampling=GREEDY, page_size=4)
        eng.submit(self.SYS + [1], max_new_tokens=4)
        eng.run()
        matched, frozen_pages = eng.radix.match(self.SYS)
        assert matched == len(self.SYS)  # both prompt pages registered
        assert eng.metrics.prefill_tokens_saved == 0
        r2 = eng.submit(self.SYS + [2], max_new_tokens=4)
        eng.step()  # admission tick
        assert eng.metrics.prefix_hits == 1
        assert eng.metrics.prefill_tokens_saved == len(self.SYS)
        # the hit is physical: slot's leading table entries ARE the
        # first request's frozen pages, refcounted tree + slot
        slot = next(i for i, s in enumerate(eng._slots) if s.active)
        assert list(eng._tables[slot, :2]) == frozen_pages
        assert all(eng.allocator.refcount(int(p)) == 2
                   for p in frozen_pages)
        results = eng.run()
        assert results[r2].tokens == greedy_by_forward(
            params, cfg, self.SYS + [2], 4)
        assert eng.decode_compile_count == 1
        assert 1 <= eng.prefill_compile_count <= len(eng.prefill_shapes)
        snap = eng.metrics.snapshot()
        assert snap["prefix_hit_rate"] == 0.5  # 1 hit / 2 admissions
        assert snap["prefill_tokens_saved"] == len(self.SYS)
        assert_pages_conserved(eng)

    def test_full_prefix_hit_still_prefills_one_token(self, tiny_llama):
        """A prompt that is ENTIRELY cached page-aligned still runs its
        last page through prefill — the first sampled token needs the
        logits at prompt_len - 1."""
        cfg, params = tiny_llama
        eng = InferenceEngine(params, cfg, max_slots=1, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4)
        r1 = eng.submit(list(self.SYS), max_new_tokens=3)
        first = eng.run()[r1].tokens
        r2 = eng.submit(list(self.SYS), max_new_tokens=3)
        results = eng.run()
        assert results[r2].tokens == first
        # only the first page is shared; the boundary page re-prefills
        assert eng.metrics.prefill_tokens_saved == 4
        assert_pages_conserved(eng)

    def test_prefix_cache_off_still_correct(self, tiny_llama):
        cfg, params = tiny_llama
        ep, paged = serve(params, cfg, prefix_cache=False)
        assert_oracle(params, cfg, SCHEDULE, paged)
        assert ep.radix is None
        assert ep.metrics.prefix_hits == 0
        assert_pages_conserved(ep)


class TestPageBudgetAdmission:
    def test_admission_waits_for_pages_then_recovers(self, tiny_llama):
        """A pool that covers only one request at a time serializes the
        two requests instead of deadlocking or corrupting — page-budget
        admission, not slot arithmetic."""
        cfg, params = tiny_llama
        # each request needs ceil((3 + 8) / 4) = 3 pages; pool holds 4
        eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4,
                              num_pages=5, prefix_cache=False)
        a = eng.submit([1, 2, 3], max_new_tokens=8)
        b = eng.submit([7, 8, 9], max_new_tokens=8)
        eng.step()
        # only one admitted: the second waits on the page budget
        assert sum(s.active for s in eng._slots) == 1
        assert eng.metrics.queue_depth == 1
        results = eng.run()
        assert_oracle(params, cfg, [([1, 2, 3], 8), ([7, 8, 9], 8)],
                      [results[a], results[b]])
        assert eng.decode_compile_count == 1
        assert_pages_conserved(eng)

    def test_eviction_unblocks_admission(self, tiny_llama):
        """Radix-held pages are reclaimed when a new request needs the
        budget: the tree evicts unpinned leaves instead of blocking."""
        cfg, params = tiny_llama
        eng = InferenceEngine(params, cfg, max_slots=1, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4,
                              num_pages=5)
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)  # registers a page
        eng.run()
        assert eng.allocator.used_count > 0  # tree still holds the page
        r = eng.submit([9, 9, 9], max_new_tokens=8)    # needs 3 of 4 pages
        results = eng.run()
        assert results[r].outcome == "ok"
        assert_pages_conserved(eng)

    def test_impossible_request_rejected_at_submit(self, tiny_llama):
        cfg, params = tiny_llama
        eng = InferenceEngine(params, cfg, max_slots=1, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4,
                              num_pages=3)
        with pytest.raises(ValueError, match="pages"):
            eng.submit([1, 2, 3], max_new_tokens=20)
        lax = InferenceEngine(params, cfg, max_slots=1, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4,
                              num_pages=3, strict_submit=False)
        rid = lax.submit([1, 2, 3], max_new_tokens=20)
        assert lax.result(rid).outcome == "rejected"

    def test_bad_page_size_raises(self, tiny_llama):
        cfg, params = tiny_llama
        with pytest.raises(ValueError, match="page_size"):
            InferenceEngine(params, cfg, page_size=0)


class TestShardedPagedServing:
    def test_tp_sharded_pool_matches_unsharded(self, tiny_llama, mm_factory):
        """ISSUE 10 acceptance: TP-sharded serving (pool KV heads over
        tp, GSPMD steps) equals the unsharded engine bit-for-bit on the
        virtual mesh — same oracle style as PR 3."""
        from scaletorch_tpu.parallel.tensor_parallel import llama_param_specs

        cfg, params = tiny_llama
        e0, expected = serve(params, cfg)
        mm = mm_factory(tp=2, dp=4)
        specs = llama_param_specs(cfg, tp_axis="tp")
        shardings = jax.tree.map(
            lambda s: NamedSharding(mm.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        params_sh = jax.tree.map(jax.device_put, params, shardings)
        eng = InferenceEngine(params_sh, cfg, max_slots=2, max_seq=32,
                              prefill_len=8, mesh=mm.mesh, tp_axis="tp",
                              sampling=GREEDY, page_size=4)
        assert eng.cache.k.sharding.spec[2] == "tp"
        ids = [eng.submit(p, max_new_tokens=n) for p, n in SCHEDULE]
        results = eng.run()
        for rid, exp in zip(ids, expected):
            assert results[rid].tokens == exp.tokens
        assert eng.decode_compile_count == 1
        assert_pages_conserved(eng)


class TestPagedMetrics:
    def test_page_gauges_move_and_export(self, tiny_llama):
        cfg, params = tiny_llama
        eng = InferenceEngine(params, cfg, max_slots=2, max_seq=32,
                              prefill_len=8, sampling=GREEDY, page_size=4)
        snap0 = eng.metrics.snapshot()
        assert snap0["pages_in_use"] == 0
        assert snap0["page_pool_free"] == eng.allocator.capacity
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.step()
        snap1 = eng.metrics.snapshot()
        assert snap1["pages_in_use"] > 0
        assert snap1["page_pool_free"] < snap0["page_pool_free"]
        eng.run()

    def test_pool_keys_ride_every_snapshot(self, tiny_llama):
        """The pool keys ride every snapshot (one telemetry JSONL /
        Prometheus schema); a drained engine with no prefix cache holds
        no page and reports no hit."""
        cfg, params = tiny_llama
        eng, _ = serve(params, cfg, [([1, 2], 2)], prefix_cache=False)
        snap = eng.metrics.snapshot()
        assert snap["pages_in_use"] == 0
        assert snap["page_pool_free"] == eng.allocator.capacity
        assert snap["prefix_hit_rate"] == 0.0
        assert snap["prefill_tokens_saved"] == 0

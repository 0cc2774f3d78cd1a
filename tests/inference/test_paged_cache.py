"""Paged KV cache primitives (ISSUE 10): allocator conservation under
randomized admit/retire/quarantine schedules, radix-tree prefix
correctness (longest match, page-boundary splits, refcount-gated
eviction), the page scatter/gather pair against the dense cache ops,
the Pallas paged-decode kernel in interpret mode against the lax
fallback oracle (one case here; its block walk, its walk from slot to
slot and the page write are ``tests/ops/test_paged_decode_blocks.py`` and
``test_paged_decode_chain.py``), the paged teacher-forced parity harness,
and the pool's ``kv_cache_bytes``. Quick tier, CPU.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.inference.decode import (
    resolve_forward_cached,
    teacher_forced_decode,
    teacher_forced_decode_paged,
)
from scaletorch_tpu.inference.kv_cache import (
    PageAllocator,
    RadixPrefixCache,
    kv_cache_bytes,
    paged_kv_cache_shape,
)
from scaletorch_tpu.models import llama, qwen3
from scaletorch_tpu.models.layers import cached_sdpa_attention, write_kv_cache
from scaletorch_tpu.ops.pallas.paged_attention import (
    TRASH_PAGE,
    paged_attention,
    paged_gather_kv,
    paged_write_kv,
    pallas_paged_decode_attention,
)
from tests.inference.compiled import compiled_forward_cached

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    dtype=jnp.float32,
)


class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        al = PageAllocator(8)
        assert al.capacity == 7  # page 0 reserved
        pages = al.alloc(3)
        assert len(pages) == 3 and TRASH_PAGE not in pages
        assert al.free_count == 4 and al.used_count == 3
        for p in pages:
            assert al.refcount(p) == 1
            al.release(p)
        assert al.free_count == al.capacity
        al.check_conservation()

    def test_alloc_is_all_or_nothing(self):
        al = PageAllocator(4)
        assert al.alloc(5) is None
        assert al.free_count == 3  # nothing was handed out
        al.check_conservation()

    def test_double_free_raises(self):
        al = PageAllocator(4)
        (p,) = al.alloc(1)
        al.release(p)
        with pytest.raises(ValueError, match="double free"):
            al.release(p)

    def test_foreign_retain_raises(self):
        al = PageAllocator(4)
        with pytest.raises(ValueError, match="unallocated"):
            al.retain(1)

    def test_refcount_sharing(self):
        al = PageAllocator(4)
        (p,) = al.alloc(1)
        al.retain(p)  # a sharing slot
        al.release(p)
        assert al.refcount(p) == 1  # still allocated
        assert al.free_count == 2
        al.release(p)
        assert al.refcount(p) == 0
        assert al.free_count == 3
        al.check_conservation()

    def test_pool_must_cover_reserved(self):
        with pytest.raises(ValueError, match="at least"):
            PageAllocator(1)

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_conservation_under_random_schedule(self, seed):
        """PR 7's outcome-conservation style for pages: across a
        randomized admit/share/register/retire/quarantine/evict schedule
        no page leaks, none is double-freed, and draining everything
        returns the pool to full capacity."""
        rng = random.Random(seed)
        al = PageAllocator(32)
        radix_refs: list[int] = []   # the tree's own references
        live: list[list[int]] = []   # per-request page references
        for _ in range(300):
            op = rng.random()
            if op < 0.35:  # admit: allocate a few pages
                pages = al.alloc(rng.randint(1, 4))
                if pages is not None:
                    # maybe share an already-registered page too
                    if radix_refs and rng.random() < 0.5:
                        shared = rng.choice(radix_refs)
                        al.retain(shared)
                        pages.append(shared)
                    live.append(pages)
            elif op < 0.55 and live:  # register some pages in the tree
                req = rng.choice(live)
                for p in req[: rng.randint(0, len(req))]:
                    if al.refcount(p) > 0:
                        al.retain(p)
                        radix_refs.append(p)
            elif op < 0.85 and live:  # retire (ok or quarantined alike)
                req = live.pop(rng.randrange(len(live)))
                for p in req:
                    al.release(p)
            elif radix_refs:  # evict one tree reference
                al.release(radix_refs.pop(rng.randrange(len(radix_refs))))
            al.check_conservation()
        for req in live:
            for p in req:
                al.release(p)
        for p in radix_refs:
            al.release(p)
        al.check_conservation()
        assert al.free_count == al.capacity


def _radix(num_pages=32, page_size=4):
    al = PageAllocator(num_pages)
    rx = RadixPrefixCache(page_size, al.retain, al.release, al.refcount)
    return al, rx


class TestRadixPrefixCache:
    def test_longest_prefix_match_is_page_aligned(self):
        al, rx = _radix()
        pages = al.alloc(2)
        rx.insert(list(range(8)), pages)
        n, got = rx.match(list(range(8)) + [99, 98])
        assert n == 8 and got == pages
        n, got = rx.match(list(range(7)))  # partial page never matches
        assert n == 4 and got == pages[:1]
        n, got = rx.match([9, 9, 9, 9])
        assert (n, got) == (0, [])

    def test_page_boundary_split(self):
        """Two prompts sharing their first page diverge at the boundary:
        the tree splits there and each keeps its own second page."""
        al, rx = _radix()
        a = al.alloc(2)
        b = al.alloc(1)
        rx.insert([1, 2, 3, 4, 5, 6, 7, 8], a)
        rx.insert([1, 2, 3, 4, 9, 9, 9, 9], [a[0], b[0]])
        assert len(rx) == 3  # shared head + two tails
        assert rx.match([1, 2, 3, 4, 5, 6, 7, 8])[1] == a
        assert rx.match([1, 2, 3, 4, 9, 9, 9, 9])[1] == [a[0], b[0]]
        # the shared head holds ONE tree reference, not two
        assert al.refcount(a[0]) == 2  # slot + tree

    def test_insert_validation(self):
        al, rx = _radix()
        pages = al.alloc(1)
        with pytest.raises(ValueError, match="page-aligned"):
            rx.insert([1, 2, 3], pages)
        with pytest.raises(ValueError, match="one page per"):
            rx.insert([1, 2, 3, 4, 5, 6, 7, 8], pages)

    def test_first_writer_wins(self):
        al, rx = _radix()
        a = al.alloc(1)
        b = al.alloc(1)
        assert rx.insert([1, 2, 3, 4], a) == 1
        assert rx.insert([1, 2, 3, 4], b) == 0  # duplicate stays private
        assert rx.match([1, 2, 3, 4])[1] == a
        assert al.refcount(b[0]) == 1  # no tree reference taken

    def test_eviction_only_at_tree_refcount(self):
        al, rx = _radix()
        pages = al.alloc(1)
        rx.insert([1, 2, 3, 4], pages)
        assert rx.evict(1) == 0  # pinned by the allocating slot
        al.release(pages[0])     # slot retires
        assert rx.evict(1) == 1
        assert al.free_count == al.capacity
        al.check_conservation()

    def test_eviction_is_lru(self):
        al, rx = _radix()
        a = al.alloc(1)
        b = al.alloc(1)
        rx.insert([1, 1, 1, 1], a)
        rx.insert([2, 2, 2, 2], b)
        al.release(a[0])
        al.release(b[0])
        rx.match([1, 1, 1, 1])  # touch a: b becomes the LRU leaf
        assert rx.evict(1) == 1
        assert rx.match([2, 2, 2, 2]) == (0, [])
        assert rx.match([1, 1, 1, 1])[0] == 4

    def test_inner_nodes_evict_after_children(self):
        al, rx = _radix()
        pages = al.alloc(3)
        rx.insert(list(range(12)), pages)
        for p in pages:
            al.release(p)
        assert rx.evict(10) == 3  # leaf, then its parent, then the root's child
        assert len(rx) == 0
        assert al.free_count == al.capacity


class TestPagedPrimitives:
    B, H, S_MAX, D, PS = 2, 2, 16, 8, 4

    def _pool_and_tables(self, key=0):
        mp = self.S_MAX // self.PS
        pool = jax.random.normal(
            jax.random.PRNGKey(key),
            (self.B * mp + 1, self.H, self.PS, self.D), jnp.float32)
        tables = (np.arange(self.B * mp, dtype=np.int32) + 1).reshape(
            self.B, mp)
        return pool, jnp.asarray(tables)

    def test_write_then_gather_matches_dense_write(self):
        k = jax.random.PRNGKey(1)
        new = jax.random.normal(k, (self.B, self.H, 3, self.D), jnp.float32)
        starts = jnp.asarray([2, 9], jnp.int32)
        positions = starts[:, None] + jnp.arange(3)[None, :]
        dense = jnp.zeros((self.B, self.H, self.S_MAX, self.D), jnp.float32)
        dense = write_kv_cache(dense, new, starts)
        pool = jnp.zeros(
            (self.B * (self.S_MAX // self.PS) + 1, self.H, self.PS, self.D),
            jnp.float32)
        _, tables = self._pool_and_tables()
        pool = paged_write_kv(pool, new, positions, tables, self.PS)
        view = paged_gather_kv(pool, tables)
        assert jnp.array_equal(view[:, :, : self.S_MAX], dense)

    def test_write_mask_redirects_to_trash(self):
        pool, tables = self._pool_and_tables()
        before = pool
        new = jnp.ones((self.B, self.H, 1, self.D), jnp.float32) * 7.0
        positions = jnp.asarray([[0], [0]], jnp.int32)
        pool = paged_write_kv(pool, new, positions, tables, self.PS,
                              write_mask=jnp.asarray([False, True]))
        # slot 1's page took the write, slot 0's pages are untouched and
        # the masked write landed on the TRASH page
        assert jnp.array_equal(pool[tables[0, 0]], before[tables[0, 0]])
        assert not jnp.array_equal(pool[tables[1, 0]], before[tables[1, 0]])
        assert not jnp.array_equal(pool[TRASH_PAGE], before[TRASH_PAGE])

    def test_positions_past_table_go_to_trash(self):
        pool, tables = self._pool_and_tables()
        before = pool
        new = jnp.full((self.B, self.H, 1, self.D), 5.0, jnp.float32)
        positions = jnp.full((self.B, 1), self.S_MAX + 3, jnp.int32)
        pool = paged_write_kv(pool, new, positions, tables, self.PS)
        for b in range(self.B):
            for t in np.asarray(tables[b]):
                assert jnp.array_equal(pool[t], before[t])

    def test_fallback_attention_bit_matches_dense(self):
        pool_k, tables = self._pool_and_tables(0)
        pool_v, _ = self._pool_and_tables(1)
        q = jax.random.normal(jax.random.PRNGKey(2),
                              (self.B, 4, 1, self.D), jnp.float32)
        pos = jnp.asarray([[5], [13]], jnp.int32)
        out = paged_attention(q, pool_k, pool_v, tables, pos,
                              page_size=self.PS, seq_limit=self.S_MAX,
                              kernel=False)
        dense = cached_sdpa_attention(
            q, paged_gather_kv(pool_k, tables)[:, :, : self.S_MAX],
            paged_gather_kv(pool_v, tables)[:, :, : self.S_MAX], pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)

    def test_pallas_kernel_interpret_matches_fallback(self):
        pool_k, tables = self._pool_and_tables(0)
        pool_v, _ = self._pool_and_tables(1)
        q = jax.random.normal(jax.random.PRNGKey(3),
                              (self.B, 4, self.D), jnp.float32)
        pos = jnp.asarray([2, 14], jnp.int32)
        out_k = pallas_paged_decode_attention(
            q, pool_k, pool_v, tables, pos, interpret=True)
        out_f = cached_sdpa_attention(
            q[:, :, None], paged_gather_kv(pool_k, tables),
            paged_gather_kv(pool_v, tables), pos[:, None])[:, :, 0]
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_f),
                                   atol=2e-6)

    def test_kernel_requires_single_token(self):
        pool_k, tables = self._pool_and_tables()
        q = jnp.zeros((self.B, 4, 3, self.D), jnp.float32)
        with pytest.raises(ValueError, match="single-token"):
            paged_attention(q, pool_k, pool_k, tables,
                            jnp.zeros((self.B, 3), jnp.int32),
                            page_size=self.PS, kernel=True)

    def test_kernel_rejects_ragged_gqa(self):
        pool_k, tables = self._pool_and_tables()
        q = jnp.zeros((self.B, 3, self.D), jnp.float32)  # 3 q-heads over 2 kv
        with pytest.raises(ValueError, match="not a multiple"):
            pallas_paged_decode_attention(
                q, pool_k, pool_k, tables, jnp.zeros((self.B,), jnp.int32))


# fp32 logits of the paged and the dense path: both sum the same terms
# over the same operand shapes (seq_limit crop), but they are two
# compiled programs (gather/scatter vs dynamic-update-slice) and XLA is
# free to fuse and order the fp32 reductions of each differently — a few
# ulps per layer (6e-7 on |logit| ~ 1.7 with the XLA in jax 0.9.0; bit
# equality held on an older XLA and cannot hold between a Pallas kernel
# and a lax gather on hardware). 1e-5 is ~50 fp32 ulps at this
# magnitude: far above reduction-order noise, far below any wrong page,
# position or mask (those move logits by >= 1e-2).
PAGED_DENSE_LOGIT_TOL = dict(rtol=1e-5, atol=1e-5)


class TestTeacherForcedPagedParity:
    """The paged read/write path reproduces the dense cache's logits
    under teacher forcing to reduction-order tolerance."""

    def _check(self, cfg, init, page_size):
        params = init(jax.random.PRNGKey(0), cfg)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                 cfg.vocab_size)
        fwd = compiled_forward_cached(resolve_forward_cached(cfg), cfg)
        dense = teacher_forced_decode(params, cfg, ids, max_seq=16,
                                      prefill_len=5, forward_fn=fwd)
        paged = teacher_forced_decode_paged(
            params, cfg, ids, page_size=page_size, max_seq=16,
            prefill_len=5, forward_fn=fwd)
        np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                                   **PAGED_DENSE_LOGIT_TOL)

    @pytest.mark.parametrize("page_size", [4, 5, 16])
    def test_llama_gqa(self, page_size):
        self._check(llama.LlamaConfig(**TINY), llama.init_params, page_size)

    def test_qwen3(self):
        self._check(qwen3.Qwen3Config(**{**TINY, "head_dim": 16}),
                    qwen3.init_params, 4)


class TestKernelPairThroughTheForwards:
    """The carried layer loop with the Mosaic pair (interpret mode) in
    place of the lax pair: prefill writes page by page, decode row by
    row and reads through the kernel at the layer index."""

    @pytest.mark.parametrize("family", ["llama", "qwen3_moe", "gpt_moe"])
    def test_logits_match_the_lax_pair(self, family):
        from scaletorch_tpu.inference.kv_cache import (
            PagedKVIO,
            init_paged_kv_cache,
        )
        from scaletorch_tpu.models import gpt_moe, qwen3_moe

        if family == "llama":
            cfg, mod = llama.LlamaConfig(**TINY), llama
        elif family == "qwen3_moe":
            cfg = qwen3_moe.Qwen3MoEConfig(
                **{**TINY, "head_dim": 16}, num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=32,
                capacity_factor=2.0)
            mod = qwen3_moe
        else:
            cfg = gpt_moe.GPTMoEConfig(
                vocab_size=64, block_size=16, n_layer=2, n_head=2,
                n_embd=32, use_moe=True, num_experts=2, top_k=1)
            mod = gpt_moe
        params = mod.init_params(jax.random.PRNGKey(0), cfg)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                 cfg.vocab_size)
        page, max_pages = 4, 4
        tables = jnp.asarray(
            (np.arange(2 * max_pages, dtype=np.int32) + 1).reshape(
                2, max_pages))

        fwd = compiled_forward_cached(mod.forward_cached, cfg)

        def run(kernel):
            io = PagedKVIO(tables, page, seq_limit=16, kernel=kernel,
                           interpret=True)
            pool = tuple(init_paged_kv_cache(
                cfg, 2 * max_pages + 1, page, dtype=jnp.float32))
            positions = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32),
                                         (2, 6))
            out, pool = fwd(
                params, ids[:, :6], cfg, pool, positions=positions,
                kv_io=io)
            chunks = [out]
            for t in range(6, 9):
                out, pool = fwd(
                    params, ids[:, t:t + 1], cfg, pool,
                    positions=jnp.full((2, 1), t, jnp.int32), kv_io=io)
                chunks.append(out)
            return jnp.concatenate(chunks, axis=1), pool

        lax_logits, lax_pool = run(False)
        kernel_logits, kernel_pool = run(True)
        np.testing.assert_allclose(
            np.asarray(kernel_logits), np.asarray(lax_logits),
            **PAGED_DENSE_LOGIT_TOL)
        for got, want in zip(kernel_pool, lax_pool):   # the writes: copies
            np.testing.assert_allclose(
                np.asarray(got[:, 1:]), np.asarray(want[:, 1:]),
                **PAGED_DENSE_LOGIT_TOL)


class TestEngineSaysWhichPair:
    """The snapshot's ``paged_pool_in_place`` gauge: the pair the two
    paged step programs are built with, decided by the platform and the
    head_dim alone (the AOT switch stands in for the platform here)."""

    @pytest.mark.parametrize("tpu,head_dim,want", [
        (False, 128, 0),   # no chip: scatter + gather
        (True, 128, 1),    # the Mosaic pair
        (True, 16, 0),     # no kernel serves a narrow head
    ], ids=["cpu", "tpu-wide", "tpu-narrow"])
    def test_gauge(self, monkeypatch, tpu, head_dim, want):
        from scaletorch_tpu.inference import InferenceEngine, SamplingParams

        monkeypatch.setenv("SCALETORCH_TPU_FORCE_PALLAS", "1" if tpu else "0")
        # an engine compiles its decode step as it is built (to place
        # its parameters), and the Mosaic pair does not lower for a CPU
        monkeypatch.setattr(InferenceEngine, "_param_orders",
                            lambda self, *_: None)
        cfg = llama.LlamaConfig(**{**TINY, "num_hidden_layers": 1},
                                head_dim=head_dim)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        engine = InferenceEngine(
            params, cfg, sampling=SamplingParams(temperature=0.0),
            max_slots=2, max_seq=16, page_size=4)
        assert engine.metrics.snapshot()["paged_pool_in_place"] == want

    @pytest.mark.parametrize("kernel_reads", [False, True],
                             ids=["lax-pair", "as-if-the-kernel"])
    def test_the_snapshot_counts_the_kernel_s_walks(self, kernel_reads):
        """``paged_slot_walks`` / ``paged_slot_walks_chained``: a decode
        step hands the kernel every slot's position (an inactive slot's
        is 0: one page), so each of a layer's calls walks every slot and
        finds all but the first one's first block started; nothing is
        counted where the lax pair reads the pool (this CPU)."""
        from scaletorch_tpu.inference import InferenceEngine, SamplingParams

        cfg = llama.LlamaConfig(**TINY)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        engine = InferenceEngine(
            params, cfg, sampling=SamplingParams(temperature=0.0),
            max_slots=3, max_seq=16, page_size=4)
        assert engine._kernel_calls_a_step == 0
        if kernel_reads:
            engine._kernel_calls_a_step = cfg.num_hidden_layers
        for prompt in ([1, 2, 3], [4, 5, 6, 7, 8]):
            engine.submit(prompt, max_new_tokens=5)
        engine.run()
        snap = engine.metrics.snapshot()
        calls = snap["decode_steps"] * cfg.num_hidden_layers * kernel_reads
        assert snap["decode_steps"] > 0
        assert snap["paged_slot_walks"] == 3 * calls
        assert snap["paged_slot_walks_chained"] == 2 * calls


class TestPoolBytes:
    """``kv_cache_bytes`` is the page pool's footprint: what the engine
    logs, what admission reasons about, what XLA allocates."""

    def test_defaults_to_the_config_dtype(self):
        cfg = llama.LlamaConfig(**TINY)
        assert cfg.dtype == jnp.float32
        assert kv_cache_bytes(cfg, 33, 16) == \
            kv_cache_bytes(cfg, 33, 16, jnp.float32)
        assert kv_cache_bytes(cfg, 33, 16, jnp.bfloat16) * 2 == \
            kv_cache_bytes(cfg, 33, 16)

    def test_paged_pool_bytes(self):
        cfg = llama.LlamaConfig(**TINY)
        shape = paged_kv_cache_shape(cfg, 33, 16)
        n = int(np.prod(shape))
        assert kv_cache_bytes(cfg, 33, 16, jnp.float32) == 2 * n * 4

    def test_engine_default_pool_holds_every_slot_full(self):
        """``num_pages=None``: max_slots * ceil(max_seq / page_size)
        pages + 1 trash page, whatever max_seq leaves of the last."""
        from scaletorch_tpu.inference import InferenceEngine
        from scaletorch_tpu.inference.kv_cache import cache_nbytes

        cfg = llama.LlamaConfig(**TINY)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        engine = InferenceEngine(params, cfg, max_slots=4, max_seq=120,
                                 page_size=16)
        assert engine.num_pages == 4 * 8 + 1
        assert cache_nbytes(engine.cache) == kv_cache_bytes(
            cfg, 4 * 8 + 1, 16)

    def test_invalid_page_size_raises(self):
        cfg = llama.LlamaConfig(**TINY)
        with pytest.raises(ValueError, match="page_size"):
            kv_cache_bytes(cfg, 8, 0)

    def test_engine_pool_matches_admission_math(self):
        """The ISSUE 15 unification: the bytes the engine's admission /
        shedding math reasons about (``kv_cache_bytes``) and the bytes
        the engine actually allocated (``cache_nbytes`` over the live
        pool) must agree exactly, for the default pool and for one cut
        below it — the jaxlint memory tier's ST1005 pins the same
        identity over the COMPILED audit entries."""
        from scaletorch_tpu.inference import InferenceEngine, SamplingParams
        from scaletorch_tpu.inference.kv_cache import cache_nbytes

        cfg = llama.LlamaConfig(**TINY)
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        for num_pages in (None, 5):
            engine = InferenceEngine(
                params, cfg, sampling=SamplingParams(temperature=0.0),
                max_slots=2, max_seq=16, page_size=4, num_pages=num_pages,
            )
            assert cache_nbytes(engine.cache) == kv_cache_bytes(
                cfg, engine.num_pages, 4, cfg.dtype)


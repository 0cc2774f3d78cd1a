"""The paged-decode kernel's block walk and the Mosaic page write, in
interpret mode: the kernel against the gather fallback over a poisoned
pool (``paged_cases.poisoned_case``) wherever blocks begin, end and are
ragged, ``paged_write`` against the scatter bit for bit. A file of its
own (ROADMAP D1): ``--dist loadfile`` gives a file to one worker, and
these cases beside the rest of ``tests/inference/test_paged_cache.py``
were that worker's whole run. Quick tier, CPU.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from scaletorch_tpu.ops.pallas.paged_attention import (
    TRASH_PAGE,
    _pages_per_block,
    paged_attention,
    paged_gather_kv,
    paged_write,
    paged_write_kv,
    pallas_paged_decode_attention,
    pallas_paged_write,
)
from tests.ops.paged_cases import interpreted_decode, poisoned_case


class TestPagedDecodeKernelBlocks:
    """The kernel walks a slot's live pages a block of
    ``_pages_per_block`` at a time (all KV heads of a page in one copy):
    parity with the gather fallback where blocks begin, end and are
    ragged, over the head layouts and page sizes the models use."""

    HKV = 2

    def _case(self, n_rep, d, page_size, even, seed=0):
        """Six slots, one at each edge of the block walk (slots 4 and 5
        share their first two pages), over a poisoned pool
        (``poisoned_case``). Returns the kernel's inputs, the
        fallback's answer and the pages of a table past its last whole
        block."""
        ppb = _pages_per_block(page_size, self.HKV, d, jnp.float32, 10 ** 6)
        max_pages = 2 * ppb if even else 2 * ppb - 3
        bk, top = ppb * page_size, max_pages * page_size - 1
        args, want = poisoned_case(
            self.HKV, n_rep, d, page_size, max_pages,
            [0, page_size - 1, page_size, bk - 1, bk, top], shared=(4, 5),
            seed=seed)
        return args, want, max_pages % ppb

    @pytest.mark.parametrize("even", [True, False],
                             ids=["whole-blocks", "short-last-block"])
    @pytest.mark.parametrize("page_size", [8, 16])
    @pytest.mark.parametrize("d", [64, 128])
    @pytest.mark.parametrize("n_rep", [1, 2, 4, 8])
    def test_block_edges_match_fallback(self, n_rep, d, page_size, even):
        args, want, remainder = self._case(n_rep, d, page_size, even)
        assert (remainder == 0) == even
        out = np.asarray(interpreted_decode()(*args))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, want, atol=5e-6)

    def test_dead_pages_never_reach_the_result(self):
        """Every page no live key sits on is NaN (TRASH included): one
        fetch of any of them, or one unmasked dead key, and the output
        is NaN. The fallback itself cannot take this pool (0 x NaN in
        its value product), which is why the oracle reads it zeroed."""
        (q, pool_k, pool_v, tables, pos), want, _ = self._case(
            2, 128, 16, False, seed=1)
        assert bool(jnp.isnan(pool_k[TRASH_PAGE]).all())
        assert bool(jnp.isnan(pool_v).any(axis=(1, 2, 3)).sum() > len(pos))
        out = np.asarray(interpreted_decode()(q, pool_k, pool_v, tables, pos))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, want, atol=5e-6)

    @pytest.mark.parametrize("page_size,hkv,d,dtype,max_pages,want", [
        (16, 8, 128, jnp.bfloat16, 96, 8),    # the serving cell: 1 MiB
        (8, 8, 128, jnp.bfloat16, 96, 16),    # 128 lanes at page 8
        (32, 8, 128, jnp.bfloat16, 48, 4),
        (16, 1, 128, jnp.bfloat16, 96, 8),    # one KV head of a tp shard
        (16, 8, 128, jnp.bfloat16, 5, 5),     # a table shorter than a block
        (16, 32, 256, jnp.float32, 96, 1),    # 512 KiB a page: the budget caps
        (4, 2, 8, jnp.float32, 4, 4),
    ])
    def test_pages_per_block_follows_shapes(self, page_size, hkv, d, dtype,
                                            max_pages, want):
        assert _pages_per_block(page_size, hkv, d, dtype, max_pages) == want

    def test_negative_position_reads_nothing(self):
        # a slot with no key at all (position -1) walks zero blocks
        (q, pool_k, pool_v, tables, pos), _, _ = self._case(2, 128, 16, True)
        out = interpreted_decode()(
            q, pool_k, pool_v, tables, jnp.full_like(pos, -1))
        assert bool((out == 0).all())


class TestPageWriteInPlace:
    """``paged_write`` (the Mosaic page write, interpret mode) against
    ``paged_write_kv`` into one layer of the whole pool, bit for bit."""

    PS, MP, D = 4, 4, 8

    def _case(self, rows, heads, starts, mask, layers, layer, seed=0):
        rng = np.random.default_rng(seed)
        slots = len(starts)
        pool = jnp.asarray(rng.standard_normal(
            (layers, slots * self.MP + 1, heads, self.PS, self.D)),
            jnp.float32)
        tables = jnp.asarray(rng.permutation(
            np.arange(1, slots * self.MP + 1)).reshape(slots, self.MP),
            jnp.int32)
        new = jnp.asarray(rng.standard_normal(
            (slots, heads, rows, self.D)), jnp.float32)
        positions = jnp.asarray(
            np.asarray(starts)[:, None] + np.arange(rows), jnp.int32)
        mask = None if mask is None else jnp.asarray(mask)
        return pool, new, positions, tables, mask, layer

    @pytest.mark.parametrize("rows,heads,starts,mask,layers,layer,trash", [
        # S = 1 (decode): any offset in the page
        (1, 2, [0, 5, 11, 7], None, 1, 0, "same"),
        (1, 4, [3, 14, 9], None, 3, 2, "same"),         # MHA-sized, layer 2
        (1, 2, [0, 5, 11, 7], [True, False, True, True], 3, 1, "same"),
        (1, 2, [0, 5, 11, 7], [True, False, True, False], 3, 1, "shared"),
        (1, 2, [0, 5, 16, 40], None, 2, 1, "shared"),   # past the table
        # S = several pages (prefill): page-aligned starts
        (12, 2, [0, 4, 0], None, 1, 0, "same"),
        (12, 4, [0, 4, 0], None, 3, 1, "same"),         # layer 1 of 3
        (10, 2, [0, 4, 0], None, 2, 1, "same"),         # a partly filled page
        (3, 2, [0, 8, 12], None, 2, 0, "same"),         # less than one page
        (10, 2, [0, 4, 0], [True, False, True], 2, 1, "shared"),
        (10, 2, [0, 4, 0], [False, False, True], 2, 1, "shared"),
        (12, 2, [0, 8, 12], None, 2, 1, "shared"),      # runs off the table
    ], ids=["row", "row-mha-layer2", "row-one-masked", "row-two-masked",
            "row-past-table", "pages", "pages-mha-layer1", "pages-ragged",
            "pages-short", "pages-one-masked", "pages-two-masked",
            "pages-past-table"])
    def test_bit_identical_to_the_scatter(self, rows, heads, starts, mask,
                                          layers, layer, trash):
        pool, new, positions, tables, mask, layer = self._case(
            rows, heads, starts, mask, layers, layer)
        want = np.asarray(paged_write_kv(
            pool, new, positions, tables, self.PS, mask, layer=layer))
        got = np.asarray(pallas_paged_write(
            pool, new, positions, tables, mask, layer=layer, interpret=True))
        # every page a slot owns, in every layer: the written layer equal
        # to the scatter's, every other layer untouched
        assert np.array_equal(got[:, 1:], want[:, 1:])
        others = [i for i in range(layers) if i != layer]
        assert np.array_equal(got[others], np.asarray(pool)[others])
        if trash == "same":     # at most one row for TRASH: same bytes
            assert np.array_equal(got[:, TRASH_PAGE], want[:, TRASH_PAGE])
        else:   # TRASH holds some writer's rows: garbage by contract
            assert np.isfinite(got[:, TRASH_PAGE]).all()
            assert not np.array_equal(got[layer, TRASH_PAGE],
                                      np.asarray(pool)[layer, TRASH_PAGE])

    def test_dispatcher_takes_the_scatter_off_the_chip(self):
        pool, new, positions, tables, mask, layer = self._case(
            1, 2, [0, 5, 11, 7], [True, False, True, True], 3, 1)
        want = paged_write_kv(pool, new, positions, tables, self.PS, mask,
                              layer=layer)
        got = paged_write(pool, new, positions, tables, mask, layer=layer)
        assert jnp.array_equal(got, want)
        forced = paged_write(pool, new, positions, tables, mask, layer=layer,
                             kernel=True, interpret=True)
        assert jnp.array_equal(forced[:, 1:], want[:, 1:])

    @pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 4)], ids=["gqa", "mha"])
    def test_kernel_reads_its_layer_of_the_whole_pool(self, hq, hkv):
        """The 5-D decode kernel at a non-zero layer against the gather
        fallback on that layer, and against the kernel on the layer
        sliced out."""
        rng = np.random.default_rng(3)
        slots, layers, layer = 3, 3, 2
        shape = (layers, slots * self.MP + 1, hkv, self.PS, self.D)
        pool_k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        pool_v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        tables = jnp.asarray(rng.permutation(
            np.arange(1, slots * self.MP + 1)).reshape(slots, self.MP),
            jnp.int32)
        q = jnp.asarray(rng.standard_normal((slots, hq, self.D)), jnp.float32)
        pos = jnp.asarray([2, 15, 9], jnp.int32)
        out = pallas_paged_decode_attention(
            q, pool_k, pool_v, tables, pos, layer=layer, interpret=True)
        fallback = paged_attention(
            q[:, :, None], pool_k, pool_v, tables, pos[:, None],
            page_size=self.PS, layer=layer, kernel=False)[:, :, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(fallback),
                                   atol=2e-6)
        sliced = pallas_paged_decode_attention(
            q, pool_k[layer], pool_v[layer], tables, pos, interpret=True)
        assert jnp.array_equal(out, sliced)
        assert jnp.array_equal(
            paged_gather_kv(pool_k, tables, layer),
            paged_gather_kv(pool_k[layer], tables))
